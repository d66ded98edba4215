"""Plain float32 reference of DeepSeek-V2-Lite's forward pass.

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")`` (on a TPU a float32 product otherwise runs in bf16): no kernel,
cache, batching, absorption or relocation. It reads the program's parameter
tree (``transformer.init_params``), whose bf16 values it widens exactly, so
the program and the reference compute from the same numbers.

One sequence, every position:

  * the leading dense layers: SwiGLU of width ``d_ff``;
  * multi-head latent attention, expanded per head: the latent c_kv passes
    the RMSNorm on the compressed KV (HF ``kv_a_layernorm``), keys and values
    are up-projected per head, rope (YaRN when configured: frequencies and
    cos/sin scale, softmax scale times mscale^2) on the query's rotary part
    and the shared rotary key, causal softmax;
  * the MoE layers: the router over all ``n_experts``, top-k of the softmax
    scores, renormalised only when ``norm_topk``; every expert the layer
    holds, ``[e_lo, e_lo + e_held)``, computed densely on every token and
    weighted by its gate (0 when not chosen); the shared experts added once.
    Given the same share as the program, it computes the same partial layer.

Departures from the HF modelling code (``modeling_deepseek.py``):

  * rope rotates the interleaved pairs (x[2i], x[2i+1]) of the rotary part.
    HF de-interleaves ``q_pe``/``k_pe`` and rotates halves, which is the
    same rotation of the same pairs written to another column order; the
    scores, which sum over the rotary dims, are equal;
  * HF projects the latent and the rotary key with one matrix
    (``kv_a_proj_with_mqa``); here they are two (``w_dkv``, ``w_kr``), the
    same columns split;
  * the top-k runs over all experts (``topk_method`` greedy, one group, as
    configured); ``routed_scaling_factor`` is 1 and is not applied;
  * the router's auxiliary loss is not computed (inference).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

F32 = jnp.float32


def _w(a) -> jax.Array:
    return jnp.asarray(a).astype(F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _w(scale)


def _swiglu(p, x):
    return (jax.nn.silu(x @ _w(p["w_gate"])) * (x @ _w(p["w_up"]))) \
        @ _w(p["w_down"])


def _yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(cfg: ModelConfig):
    """(inv_freq (d_rope/2,), cos/sin scale, softmax scale)."""
    m = cfg.mla
    d, base = m.d_rope, cfg.rope_theta
    inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    scale = (m.d_nope + m.d_rope) ** -0.5
    y = m.yarn
    if y is None:
        return inv.astype(np.float32), 1.0, scale

    def dim(rot):
        return (d * math.log(y.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim(y.beta_fast)), 0)
    high = min(math.ceil(dim(y.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = inv / y.factor * (1 - mask) + inv * mask
    cs = _yarn_mscale(y.factor, y.mscale) / _yarn_mscale(y.factor,
                                                         y.mscale_all_dim)
    if y.mscale_all_dim:
        scale *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return inv.astype(np.float32), cs, scale


def _rope(x, pos, inv, cs):
    """x (S, ..., D): rotate pairs (x[2i], x[2i+1]) by pos * inv[i]."""
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv)           # (S, D/2)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = cs * jnp.cos(ang), cs * jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _attention(p, x, cfg: ModelConfig, pos):
    m, h = cfg.mla, cfg.n_heads
    s = x.shape[0]
    inv, cs, scale = rope_tables(cfg)
    q = (x @ _w(p["wq"])).reshape(s, h, m.d_nope + m.d_rope)
    q_nope, q_pe = q[..., :m.d_nope], _rope(q[..., m.d_nope:], pos, inv, cs)
    c_kv = _rms(x @ _w(p["w_dkv"]), p["kv_norm"], cfg.norm_eps)
    k_pe = _rope(x @ _w(p["w_kr"]), pos, inv, cs)               # (S, d_rope)
    kv = (c_kv @ _w(p["w_ukv"])).reshape(s, h, m.d_nope + m.d_v)
    k_nope, v = kv[..., :m.d_nope], kv[..., m.d_nope:]
    scores = (jnp.einsum("shd,thd->hst", q_nope, k_nope)
              + jnp.einsum("shd,td->hst", q_pe, k_pe)) * scale
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("hst,thd->shd", probs, v).reshape(s, -1)
    return out @ _w(p["wo"])


def _moe(p, x, cfg: ModelConfig, e_lo: int):
    mc = cfg.moe
    probs = jax.nn.softmax(x @ _w(p["router"]), -1)             # (S, E)
    top, idx = jax.lax.top_k(probs, mc.top_k)
    if mc.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)
    out = jnp.zeros_like(x)
    for j in range(p["w_gate"].shape[0]):                       # held experts
        ex = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
        out = out + gates[:, e_lo + j, None] * _swiglu(ex, x)
    if mc.n_shared:
        out = out + _swiglu(p["shared"], x)
    return out


def _layers(tree):
    n = jax.tree.leaves(tree)[0].shape[0]
    return [jax.tree.map(lambda a: a[i], tree) for i in range(n)]


def forward(params, cfg: ModelConfig, tokens, e_lo: int = 0) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V). ``e_lo``: the first expert the
    parameters hold (their count is the stacked expert axis)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        pos = jnp.arange(tokens.shape[0])
        x = _w(params["embed"])[tokens]
        blocks = ([(lp, False) for lp in _layers(params["dense_layers"])]
                  if "dense_layers" in params else [])
        blocks += [(lp, True) for lp in _layers(params["layers"])]
        for lp, moe in blocks:
            x = x + _attention(lp["attn"], _rms(x, lp["norm1"],
                                                cfg.norm_eps), cfg, pos)
            hn = _rms(x, lp["norm2"], cfg.norm_eps)
            x = x + (_moe(lp["moe"], hn, cfg, e_lo) if moe
                     else _swiglu(lp["mlp"], hn))
        x = _rms(x, params["final_norm"], cfg.norm_eps)
        return x @ _w(params["lm_head"])
