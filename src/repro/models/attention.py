"""Attention mixers: GQA (grouped-query) and MLA (multi-head latent).

Both support three execution modes driven by the same parameters:
  * full-sequence (training / prefill): causal or bidirectional;
  * cached: new tokens written into a (B, S_max) cache at their positions,
    attending over every entry at or below their own position (one token:
    decode; a run of tokens: a prefill chunk that sees the chunks before);
  * cross-attention (enc-dec): keys/values from encoder output, no mask.

MLA (deepseek-v2) caches the compressed latent c_kv (kv_lora_rank, after
its RMSNorm) + the shared rotary key (d_rope) instead of full per-head K/V
— the same "store the compact relocated form, expand on use" shape as the
paper's Catwalk dendrite, at KV-cache granularity (576 vs 2*H*128
floats/token). Two forms compute it from one set of parameters:
``mla_attend`` expands keys and values per head (full sequences, prefill
chunks); ``mla_attend_absorbed`` folds the up-projections into the query
and the output and attends over the latent itself (decode).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig
from repro.models import layers as L

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Decode KV cache. ``pos`` comes in two layouts:

    * ``()`` scalar — all rows share one write position (static batching:
      every request prefilled together, advancing in lockstep);
    * ``(B,)`` vector — per-slot positions (continuous batching:
      each batch row is an independent decode slot, re-fillable
      mid-flight; row r writes at ``pos[r]`` and attends over its own
      ``pos[r] + s`` valid entries only).

    Both advance by ``s`` per call; every cache op below branches on
    ``pos.ndim`` so the two layouts share one code path.
    """

    k: jax.Array          # GQA: (B, S, Hkv, Dh) | MLA: (B, S, kv_lora)
    v: jax.Array          # GQA: (B, S, Hkv, Dh) | MLA: (B, S, d_rope)
    pos: jax.Array        # () | (B,) int32 — tokens already in cache


def _cache_positions(pos: jax.Array, s: int) -> jax.Array:
    """Absolute positions of this call's ``s`` new tokens: (1, s) for a
    scalar ``pos`` (shared), (B, s) for per-slot ``pos``."""
    base = jnp.arange(s)[None, :].astype(jnp.int32)
    return pos[:, None] + base if pos.ndim == 1 else pos + base


def _cache_update(buf: jax.Array, new: jax.Array, pos: jax.Array
                  ) -> jax.Array:
    """Write ``new`` (B, s, ...) into ``buf`` (B, S, ...) at ``pos``.

    Scalar ``pos``: every row at ``pos``; per-slot ``(B,)`` pos: row r at
    ``pos[r]``. Writes past S drop (``mode='drop'``), so an over-budget row
    (or an inactive one, whose position is set past S) is inert rather
    than wrapping around or clamping onto live entries."""
    b, s = new.shape[:2]
    t_idx = _cache_positions(pos, s)
    b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
    return buf.at[b_idx, t_idx].set(new, mode="drop")


def _key_mask(q_pos: jax.Array, s_keys: int) -> jax.Array:
    """(B|1, s, S) causal mask by absolute position: key t is visible to
    the query at position p when t <= p (a cache entry above every query's
    position is stale or empty)."""
    return jnp.arange(s_keys)[None, None, :] <= q_pos[:, :, None]


# =============================================================== GQA ======
def gqa_init(key, cfg: ModelConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(kq, d, cfg.n_heads * hd, dtype),
        "wk": L.dense_init(kk, d, cfg.n_kv_heads * hd, dtype),
        "wv": L.dense_init(kv, d, cfg.n_kv_heads * hd, dtype),
        "wo": L.dense_init(ko, cfg.n_heads * hd, d, dtype),
    }


def _sdpa(q, k, v, *, causal: bool, q_pos=None, mask=None):
    """q (B,Sq,H,D); k/v (B,Sk,G,D) with H = G*rep. f32 softmax. ``mask``
    (B|1, Sq, Sk): keys each query may see (decode)."""
    b, sq, h, dh = q.shape
    g = k.shape[2]
    rep = h // g
    qf = q.reshape(b, sq, g, rep, dh).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qf, kf) / np.sqrt(dh)
    sk = k.shape[1]
    if causal:
        qp = (jnp.arange(sq) if q_pos is None else q_pos)
        causal_mask = qp[:, None] >= jnp.arange(sk)[None, :]
        scores = jnp.where(causal_mask[None, None, None], scores, NEG_INF)
    if mask is not None:                          # decode: by position
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", probs, vf)
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def gqa_apply(p, x, cfg: ModelConfig, *, causal=True, positions=None,
              cache: Optional[KVCache] = None, kv_input=None):
    """x (B,S,D). kv_input: encoder output for cross-attention (no rope).

    With ``cache``: appends this call's K/V at cache.pos and attends over
    the full cache (decode). Returns (out, new_cache | None)."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    if positions is None:
        positions = (jnp.arange(s)[None, :].astype(jnp.int32) if cache is None
                     else _cache_positions(cache.pos, s))
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, hd)
    src = x if kv_input is None else kv_input
    k = (src @ p["wk"]).reshape(b, src.shape[1], cfg.n_kv_heads, hd)
    v = (src @ p["wv"]).reshape(b, src.shape[1], cfg.n_kv_heads, hd)
    if kv_input is None:                              # self-attn: rope
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        k_all = _cache_update(cache.k, k, cache.pos)
        v_all = _cache_update(cache.v, v, cache.pos)
        new_cache = KVCache(k_all, v_all, cache.pos + s)
        out = _sdpa(q, k_all, v_all, causal=False,
                    mask=_key_mask(positions, k_all.shape[1]))
    else:
        out = _sdpa(q, k, v, causal=causal and kv_input is None)
    return out.reshape(b, s, -1) @ p["wo"], new_cache


def gqa_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, cfg.n_kv_heads, hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


# =============================================================== MLA ======
def mla_init(key, cfg: ModelConfig, dtype):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    kq, kd, ku, kr, ko = jax.random.split(key, 5)
    return {
        "wq": L.dense_init(kq, d, h * (m.d_nope + m.d_rope), dtype),
        "w_dkv": L.dense_init(kd, d, m.kv_lora_rank, dtype),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank),
        "w_ukv": L.dense_init(ku, m.kv_lora_rank,
                              h * (m.d_nope + m.d_v), dtype),
        "w_kr": L.dense_init(kr, d, m.d_rope, dtype),
        "wo": L.dense_init(ko, h * m.d_v, d, dtype),
    }


def mla_rope(cfg: ModelConfig):
    """(rotary frequencies or None, cos/sin scale, softmax scale): YaRN's
    when the config sets it (deepseek-v2: softmax scale 1/sqrt(d_nope +
    d_rope) times mscale(factor, mscale_all_dim)^2)."""
    m = cfg.mla
    scale = 1.0 / np.sqrt(m.d_nope + m.d_rope)
    if m.yarn is None:
        return None, 1.0, scale
    y = m.yarn
    cs = L.yarn_mscale(y.factor, y.mscale) / L.yarn_mscale(
        y.factor, y.mscale_all_dim)
    if y.mscale_all_dim:
        scale *= L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return L.yarn_freqs(m.d_rope, cfg.rope_theta, y), cs, scale


def mla_project(p, x, cfg: ModelConfig, positions):
    """x (B,S,D) -> q_nope (B,S,H,dn), q_rope (B,S,H,dr), and what the
    cache holds per token: the latent c_kv (B,S,R) after its RMSNorm
    (deepseek's ``kv_a_layernorm``) and the shared rotary key (B,S,dr)."""
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    freqs, cs, _ = mla_rope(cfg)
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, m.d_nope + m.d_rope)
    q_nope = q[..., :m.d_nope]
    q_rope = L.apply_rope(q[..., m.d_nope:], positions, cfg.rope_theta,
                          freqs, cs)
    c_kv = L.rmsnorm(p["kv_norm"], x @ p["w_dkv"], cfg.norm_eps)
    k_rope = L.apply_rope((x @ p["w_kr"])[:, :, None, :], positions,
                          cfg.rope_theta, freqs, cs)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_attend(p, q_nope, q_rope, c_kv, k_rope, q_pos, cfg: ModelConfig):
    """Expanded attention: every key and value up-projected per head from
    the latent (B,T,R) through ``w_ukv``. Queries (B,S,H,·) at positions
    ``q_pos`` (B|1,S) see keys t <= their position. Returns (B,S,D)."""
    m: MLAConfig = cfg.mla
    b, s, h, _ = q_nope.shape
    t = c_kv.shape[1]
    kv = (c_kv @ p["w_ukv"]).reshape(b, t, h, m.d_nope + m.d_v)
    k_nope, v = kv[..., :m.d_nope], kv[..., m.d_nope:]
    f32 = jnp.float32
    scores = jnp.einsum("bshd,bthd->bhst", q_nope.astype(f32),
                        k_nope.astype(f32))
    scores += jnp.einsum("bshd,btd->bhst", q_rope.astype(f32),
                         k_rope.astype(f32))
    scores *= mla_rope(cfg)[2]
    scores = jnp.where(_key_mask(q_pos, t)[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(f32))
    return out.reshape(b, s, -1).astype(q_nope.dtype) @ p["wo"]


def mla_attend_absorbed(p, q_nope, q_rope, c_kv, k_rope, q_pos,
                        cfg: ModelConfig):
    """The same attention with ``w_ukv`` absorbed (decode): the key
    up-projection folded into the query, scores and the weighted sum taken
    over the latent itself, the value up-projection applied after. Nothing
    of shape (B,T,H,·) is formed. bf16 operands, float32 accumulation and
    softmax."""
    m: MLAConfig = cfg.mla
    b, s, h, _ = q_nope.shape
    t, r = c_kv.shape[1], m.kv_lora_rank
    dt = c_kv.dtype
    w = p["w_ukv"].reshape(r, h, m.d_nope + m.d_v)
    q_lat = L.einsum_f32("bshn,rhn->bshr", q_nope,
                         w[..., :m.d_nope]).astype(dt)
    scores = L.einsum_f32("bshr,btr->bhst", q_lat, c_kv)
    scores += L.einsum_f32("bshd,btd->bhst", q_rope.astype(dt), k_rope)
    scores *= mla_rope(cfg)[2]
    scores = jnp.where(_key_mask(q_pos, t)[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    o_lat = L.einsum_f32("bhst,btr->bshr", probs, c_kv).astype(dt)
    out = L.einsum_f32("bshr,rhv->bshv", o_lat, w[..., m.d_nope:])
    return out.reshape(b, s, -1).astype(dt) @ p["wo"]


def mla_apply(p, x, cfg: ModelConfig, *, positions=None,
              cache: Optional[KVCache] = None):
    """Multi-head latent attention, expanded; cache holds (c_kv, k_rope).
    With ``cache``, this call's tokens are written at cache.pos and attend
    over every cache entry at or below their own position (decode, or a
    prefill chunk that sees the chunks before it)."""
    s = x.shape[1]
    if positions is None:
        positions = (jnp.arange(s)[None, :].astype(jnp.int32) if cache is None
                     else _cache_positions(cache.pos, s))
    q_nope, q_rope, c_kv, k_rope = mla_project(p, x, cfg, positions)
    new_cache = None
    if cache is not None:
        c_kv = _cache_update(cache.k, c_kv, cache.pos)
        k_rope = _cache_update(cache.v, k_rope, cache.pos)
        new_cache = KVCache(c_kv, k_rope, cache.pos + s)
    return (mla_attend(p, q_nope, q_rope, c_kv, k_rope, positions, cfg),
            new_cache)


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return KVCache(jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                   jnp.zeros((batch, max_len, m.d_rope), dtype),
                   jnp.zeros((), jnp.int32))
