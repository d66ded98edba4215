"""Model assembly: every assigned architecture from one set of blocks.

Families (configs/base.py):
  dense / vlm          - GQA decoder (vlm prepends projected patch embeds)
  moe                  - GQA or MLA attention + MoE FFN (+shared/+residual)
  ssm                  - Mamba2 (SSD) stack, attention-free
  hybrid               - Mamba2 backbone + ONE shared GQA block every
                         ``period`` layers (Zamba2)
  audio                - encoder-decoder; encoder consumes frame embeddings
                         (frontend stub), decoder is a causal GQA stack with
                         cross-attention

Layers are scanned (jax.lax.scan over stacked parameters) so HLO size and
compile time are depth-independent — essential for the 40-cell dry-run —
with per-block activation rematerialization (cfg.remat='block').
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


def _dt(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


# ============================================================ init =======
def _block_init(key, cfg: ModelConfig, dtype, dense: bool = False):
    """One decoder block (attention + FFN/MoE + norms); ``dense``: a plain
    SwiGLU of width ``d_ff`` in place of the MoE (the leading layers)."""
    k1, k2 = jax.random.split(key)
    p = {"norm1": L.rmsnorm_init(cfg.d_model),
         "norm2": L.rmsnorm_init(cfg.d_model)}
    if cfg.mla is not None:
        p["attn"] = A.mla_init(k1, cfg, dtype)
    else:
        p["attn"] = A.gqa_init(k1, cfg, dtype)
    if cfg.moe is not None and not dense:
        p["moe"] = M.moe_init(k2, cfg.d_model, cfg.moe, dtype)
        if cfg.moe.dense_residual:
            p["mlp"] = L.mlp_init(jax.random.fold_in(k2, 7), cfg.d_model,
                                  cfg.d_ff, dtype)
    else:
        p["mlp"] = L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _enc_block_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {"norm1": L.rmsnorm_init(cfg.d_model),
            "norm2": L.rmsnorm_init(cfg.d_model),
            "attn": A.gqa_init(k1, cfg, dtype),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)}


def _dec_block_init(key, cfg: ModelConfig, dtype):
    """Decoder block with cross-attention (enc-dec family)."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {"norm1": L.rmsnorm_init(cfg.d_model),
            "norm_x": L.rmsnorm_init(cfg.d_model),
            "norm2": L.rmsnorm_init(cfg.d_model),
            "attn": A.gqa_init(k1, cfg, dtype),
            "xattn": A.gqa_init(k3, cfg, dtype),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)}


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = _dt(cfg)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": L.embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": L.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[1], cfg.d_model,
                                         cfg.vocab_size, dtype)

    def stack(init_fn, n, key):
        return jax.vmap(lambda k: init_fn(k, cfg, dtype))(
            jax.random.split(key, n))

    if cfg.family in ("ssm", "hybrid"):
        params["layers"] = stack(lambda k, c, d: {
            "norm1": L.rmsnorm_init(c.d_model),
            "ssm": S.ssm_init(k, c, d)}, cfg.n_layers, keys[2])
        if cfg.family == "hybrid":
            params["shared"] = _block_init(keys[3], cfg, dtype)
    elif cfg.family == "audio":
        params["layers"] = stack(_dec_block_init, cfg.n_layers, keys[2])
        params["encoder"] = stack(_enc_block_init,
                                  cfg.encdec.n_encoder_layers, keys[3])
        params["frame_proj"] = L.dense_init(keys[4], cfg.frontend.d_embed,
                                            cfg.d_model, dtype)
    else:
        nd = cfg.n_dense_layers
        params["layers"] = stack(_block_init, cfg.n_layers - nd, keys[2])
        if nd:
            params["dense_layers"] = stack(
                lambda k, c, d: _block_init(k, c, d, dense=True), nd,
                keys[5])
        if cfg.family == "vlm":
            params["patch_proj"] = L.dense_init(
                keys[4], cfg.frontend.d_embed, cfg.d_model, dtype)
    return params


# ======================================================== forward ========
def _ffn_apply(p, h, cfg: ModelConfig):
    """The block's FFN: MoE (+ dense residual) or a dense SwiGLU.
    Returns (out, aux_loss)."""
    if "moe" not in p:
        return L.mlp_apply(p["mlp"], h), jnp.zeros((), jnp.float32)
    if cfg.moe.dispatch == "catwalk_ep":
        out, stats = M.moe_apply_ep(p["moe"], h, cfg.moe,
                                    fsdp=cfg.moe.ep_fsdp)
    else:
        out, stats = M.moe_apply(p["moe"], h, cfg.moe)
    if cfg.moe.dense_residual:
        out = out + L.mlp_apply(p["mlp"], h)
    return out, stats["aux_loss"]


def _block_apply(p, x, cfg: ModelConfig, cache=None, enc_out=None):
    """Returns (x, aux_loss, new_cache)."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a, new_cache = A.mla_apply(p["attn"], h, cfg, cache=cache)
    else:
        a, new_cache = A.gqa_apply(p["attn"], h, cfg, cache=cache)
    x = x + a
    if enc_out is not None:
        h = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
        a, _ = A.gqa_apply(p["xattn"], h, cfg, kv_input=enc_out,
                           causal=False)
        x = x + a
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    out, aux = _ffn_apply(p, h, cfg)
    return x + out, aux, new_cache


def _ssm_block_apply(p, x, cfg: ModelConfig, cache=None):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    out, new_cache = S.ssm_apply(p["ssm"], h, cfg, cache=cache)
    return x + out, new_cache


def _maybe_remat(fn, cfg: ModelConfig):
    # H7 (save gathered expert weights across remat, policy
    # save_only_these_names('moe_gathered')) cut arctic collectives 13%
    # but cost +110 GB/chip temp (35 layers of gathered experts pinned) —
    # REFUTED on the HBM budget; plain block remat stands. See §Perf log.
    return jax.checkpoint(fn) if cfg.remat == "block" else fn


def forward(params, cfg: ModelConfig, tokens: jax.Array, *,
            patches: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            logits_mode: str = "all") -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, aux_loss).

    tokens (B, S); patches (B, Np, d_embed) for vlm; frames (B, Se,
    d_embed) for audio enc-dec. ``logits_mode='last'`` projects only the
    final position (prefill: avoids the (B, S, V) logits tensor).
    """
    x = L.embed_lookup(params["embed"], tokens)
    n_prefix = 0
    if cfg.family == "vlm" and patches is not None:
        px = patches.astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([px, x], axis=1)
        n_prefix = px.shape[1]

    enc_out = None
    if cfg.family == "audio":
        enc = frames.astype(x.dtype) @ params["frame_proj"]

        def enc_body(h, lp):
            n = L.rmsnorm(lp["norm1"], h, cfg.norm_eps)
            a, _ = A.gqa_apply(lp["attn"], n, cfg, causal=False)
            h = h + a
            n = L.rmsnorm(lp["norm2"], h, cfg.norm_eps)
            return h + L.mlp_apply(lp["mlp"], n), None

        enc_out, _ = jax.lax.scan(_maybe_remat(enc_body, cfg), enc,
                                  params["encoder"])

    def _act_constrain(h):
        if not cfg.act_sp:
            return h
        from repro.sharding.specs import dp_spec_names, maybe_wsc
        return maybe_wsc(h, dp_spec_names(), "model", None)   # SP on seq

    if cfg.family in ("ssm", "hybrid"):
        period = cfg.hybrid.period if cfg.hybrid else 0
        flags = (jnp.arange(cfg.n_layers) % max(period, 1)
                 == max(period, 1) - 1) if period else \
            jnp.zeros((cfg.n_layers,), bool)

        def body(h, xs):
            lp, use_shared = xs
            h, _ = _ssm_block_apply(lp, h, cfg)
            if cfg.family == "hybrid":
                def shared(hh):
                    out, _, _ = _block_apply(params["shared"], hh, cfg)
                    return out
                h = jax.lax.cond(use_shared, shared, lambda hh: hh, h)
            return _act_constrain(h), jnp.zeros((), jnp.float32)

        x, auxs = jax.lax.scan(_maybe_remat(body, cfg), x,
                               (params["layers"], flags))
    else:
        def body(h, lp):
            h, aux, _ = _block_apply(lp, h, cfg, enc_out=enc_out)
            return _act_constrain(h), aux

        if "dense_layers" in params:
            x, _ = jax.lax.scan(_maybe_remat(body, cfg), x,
                                params["dense_layers"])
        x, auxs = jax.lax.scan(_maybe_remat(body, cfg), x, params["layers"])

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    if logits_mode == "last":
        return x[:, -1:] @ head, jnp.sum(auxs)
    logits = x @ head
    if n_prefix:
        logits = logits[:, n_prefix:]
    return logits, jnp.sum(auxs)


# ========================================================= serving =======
class ServeState(NamedTuple):
    layer_caches: Any          # stacked per-layer caches (leading axis L)
    shared_cache: Any          # hybrid shared block cache (or None)
    enc_out: Any               # enc-dec encoder output (or None)
    pos: jax.Array             # () int32, or (B,) per slot


def init_serve_state(params, cfg: ModelConfig, batch: int, max_len: int, *,
                     frames: Optional[jax.Array] = None) -> ServeState:
    dtype = _dt(cfg)

    def stacked(fn):
        one = fn()
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one)

    shared_cache = None
    enc_out = None
    if cfg.family in ("ssm", "hybrid"):
        caches = stacked(lambda: S.ssm_cache_init(cfg, batch, dtype))
        if cfg.family == "hybrid":
            # one cache per shared-block APPLICATION SITE (weights are
            # shared; the KV streams are not)
            n_sites = cfg.n_layers // cfg.hybrid.period
            one = A.gqa_cache_init(cfg, batch, max_len, dtype)
            shared_cache = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_sites,) + a.shape), one)
    elif cfg.mla is not None:
        caches = stacked(lambda: A.mla_cache_init(cfg, batch, max_len, dtype))
    else:
        caches = stacked(lambda: A.gqa_cache_init(cfg, batch, max_len, dtype))
    if cfg.family == "audio":
        enc = frames.astype(dtype) @ params["frame_proj"]

        def enc_body(h, lp):
            n = L.rmsnorm(lp["norm1"], h, cfg.norm_eps)
            a, _ = A.gqa_apply(lp["attn"], n, cfg, causal=False)
            h = h + a
            n = L.rmsnorm(lp["norm2"], h, cfg.norm_eps)
            return h + L.mlp_apply(lp["mlp"], n), None

        enc_out, _ = jax.lax.scan(enc_body, enc, params["encoder"])
    return ServeState(caches, shared_cache, enc_out,
                      jnp.zeros((), jnp.int32))


def per_slot_state(state: ServeState, batch: int) -> ServeState:
    """Switch a fresh serve state to per-slot cache positions.

    Replaces every scalar position with its ``(B,)`` vector layout
    (``attention.KVCache.pos``) so each batch row advances independently —
    the state layout continuous batching decodes against
    (``repro.serve.engine.Engine``): a freed row is re-filled by
    ``prefill_chunk``, which sets its position, while the other rows keep
    decoding; entries above a row's position are never attended, so none
    is cleared. ``decode_step`` is layout-agnostic (the cache ops branch on
    ``pos.ndim``), so the same compiled step serves both layouts.

    Only attention-family caches position independent rows this way; SSM
    recurrences and the hybrid shared block carry no positional cache
    (their state is per-row already, but the engine's prefill contract
    differs), and audio holds a per-request encoder output — those
    families keep the static engine path.
    """
    if not isinstance(state.layer_caches, A.KVCache):
        raise ValueError(
            "per-slot positions need attention KV caches; family with "
            f"caches {type(state.layer_caches).__name__} is served "
            "statically")
    if state.enc_out is not None or state.shared_cache is not None:
        raise ValueError("per-slot positions: audio/hybrid states are "
                         "served statically")
    n_layers = state.layer_caches.pos.shape[0]
    return ServeState(
        state.layer_caches._replace(
            pos=jnp.zeros((n_layers, batch), jnp.int32)),
        state.shared_cache, state.enc_out,
        jnp.zeros((batch,), jnp.int32))


def decode_step(params, cfg: ModelConfig, state: ServeState,
                tokens: jax.Array) -> Tuple[jax.Array, ServeState]:
    """One decode step. tokens (B, 1) -> logits (B, V), new state. MLA
    models decode in the absorbed form (``_decode_mla``)."""
    return decode_rows(params, cfg, state, tokens)


def decode_rows(params, cfg: ModelConfig, state: ServeState,
                tokens: jax.Array, active: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, ServeState]:
    """``decode_step`` over some rows: ``active`` (B,) bool, per-slot
    states only, the rows that decode; the others neither write their
    cache nor advance (a slot mid-prefill, or free)."""
    if cfg.mla is not None:
        return _decode_mla(params, cfg, state, tokens, active)
    if active is None:
        return _decode_static(params, cfg, state, tokens)
    caches = state.layer_caches
    s_max = caches.k.shape[2]
    parked = caches._replace(pos=jnp.where(active[None], caches.pos, s_max))
    logits, new = _decode_static(
        params, cfg, state._replace(layer_caches=parked), tokens)
    return logits, _advance(new, state.pos, active)


def _decode_static(params, cfg: ModelConfig, state: ServeState, tokens
                   ) -> Tuple[jax.Array, ServeState]:
    """Every row advances one token (scalar or per-slot positions)."""
    x = L.embed_lookup(params["embed"], tokens)
    pos = state.pos

    if cfg.family in ("ssm", "hybrid"):
        def ssm_body(h, xs):
            lp, cache = xs
            hn = L.rmsnorm(lp["norm1"], h, cfg.norm_eps)
            out, new_cache = S.ssm_apply(lp["ssm"], hn, cfg, cache=cache)
            return h + out, new_cache

        if cfg.family == "ssm":
            x, new_caches = jax.lax.scan(ssm_body, x, (params["layers"],
                                                       state.layer_caches))
            new_state = ServeState(new_caches, None, state.enc_out, pos + 1)
        else:
            # hybrid: group-scan — ``period`` SSM layers then the shared
            # attention block with that site's own KV cache
            p_ = cfg.hybrid.period
            g = cfg.n_layers // p_
            tail = cfg.n_layers - g * p_

            def split_gp(a):
                return (a[:g * p_].reshape((g, p_) + a.shape[1:]),
                        a[g * p_:])
            grp_layers = jax.tree.map(lambda a: split_gp(a)[0],
                                      params["layers"])
            tail_layers = jax.tree.map(lambda a: split_gp(a)[1],
                                       params["layers"])
            grp_caches = jax.tree.map(lambda a: split_gp(a)[0],
                                      state.layer_caches)
            tail_caches = jax.tree.map(lambda a: split_gp(a)[1],
                                       state.layer_caches)

            def group_body(h, xs):
                glp, gcache, shc = xs
                h, new_gcache = jax.lax.scan(ssm_body, h, (glp, gcache))
                h, _, new_shc = _block_apply(params["shared"], h, cfg,
                                             cache=shc)
                return h, (new_gcache, new_shc)

            x, (new_grp_caches, new_shared) = jax.lax.scan(
                group_body, x, (grp_layers, grp_caches,
                                state.shared_cache))
            if tail:
                x, new_tail_caches = jax.lax.scan(
                    ssm_body, x, (tail_layers, tail_caches))
            else:
                new_tail_caches = tail_caches
            new_caches = jax.tree.map(
                lambda gc, tc: jnp.concatenate(
                    [gc.reshape((g * p_,) + gc.shape[2:]), tc], axis=0),
                new_grp_caches, new_tail_caches)
            new_state = ServeState(new_caches, new_shared, state.enc_out,
                                   pos + 1)
    else:
        def body(h, xs):
            lp, cache = xs
            h, _, new_cache = _block_apply(lp, h, cfg, cache=cache,
                                           enc_out=state.enc_out)
            return h, new_cache

        x, new_caches = jax.lax.scan(body, x, (params["layers"],
                                               state.layer_caches))
        new_state = ServeState(new_caches, state.shared_cache, state.enc_out,
                               pos + 1)

    return _head(params, cfg, x[:, 0]), new_state


def _head(params, cfg: ModelConfig, x):
    """Final norm and output head: float32 logits (..., V)."""
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return L.einsum_f32("...d,dv->...v", x, head)


def _advance(state: ServeState, pos, active) -> ServeState:
    """Per-slot positions ``pos`` advanced by one in the ``active`` rows."""
    new_pos = pos + active.astype(pos.dtype)
    caches = state.layer_caches
    return state._replace(
        pos=new_pos, layer_caches=caches._replace(
            pos=jnp.broadcast_to(new_pos, caches.pos.shape)))


def _decode_mla(params, cfg: ModelConfig, state: ServeState, tokens,
                active) -> Tuple[jax.Array, ServeState]:
    """Absorbed-MLA decode over the stacked latent caches (L, B, S, R) and
    (L, B, S, d_rope), which ride the layer loop as carried state: each
    layer writes its token's entry at the row's position in place and
    attends over the latent (``attention.mla_attend_absorbed``). The dense
    leading layers run first, then the scanned MoE layers."""
    caches = state.layer_caches
    b, s_max = tokens.shape[0], caches.k.shape[2]
    pos = jnp.broadcast_to(state.pos, (b,))
    live = jnp.ones((b,), bool) if active is None else active
    wpos = jnp.where(live, pos, s_max)               # inactive: dropped
    q_pos = pos[:, None]
    rows = jnp.arange(b)

    def layer(carry, lp, i):
        h, ckv, kr = carry
        hn = L.rmsnorm(lp["norm1"], h, cfg.norm_eps)
        q_nope, q_rope, c, r = A.mla_project(lp["attn"], hn, cfg, q_pos)
        ckv = ckv.at[i, rows, wpos].set(c[:, 0], mode="drop")
        kr = kr.at[i, rows, wpos].set(r[:, 0], mode="drop")
        h = h + A.mla_attend_absorbed(lp["attn"], q_nope, q_rope, ckv[i],
                                      kr[i], q_pos, cfg)
        out, _ = _ffn_apply(lp, L.rmsnorm(lp["norm2"], h, cfg.norm_eps), cfg)
        return h + out, ckv, kr

    carry = (L.embed_lookup(params["embed"], tokens), caches.k, caches.v)
    nd = cfg.n_dense_layers
    for i in range(nd):
        carry = layer(carry, jax.tree.map(lambda a: a[i],
                                          params["dense_layers"]), i)
    carry, _ = jax.lax.scan(
        lambda c, xs: (layer(c, xs[0], xs[1]), None), carry,
        (params["layers"], nd + jnp.arange(cfg.n_layers - nd)))
    h, ckv, kr = carry
    new = state._replace(layer_caches=A.KVCache(ckv, kr, caches.pos))
    if state.pos.ndim == 0:
        new = new._replace(pos=state.pos + 1, layer_caches=new.layer_caches
                           ._replace(pos=caches.pos + 1))
    else:
        new = _advance(new, state.pos, live)
    return _head(params, cfg, h[:, 0]), new


def prefill_chunk(params, cfg: ModelConfig, state: ServeState,
                  tokens: jax.Array, slot, start, n
                  ) -> Tuple[jax.Array, ServeState]:
    """Prefill one run of a slot's prompt: ``tokens`` (C,) at positions
    ``start, start + 1, ...``, of which the first ``n`` are real (the rest
    pad the chunk to its compiled length). Writes the slot's cache entries
    for them, attending over the slot's earlier entries (the chunks before
    it), sets the slot's position to ``start + n``, and returns the logits
    (V,) at position ``start + n - 1`` and the new state. Only the slot's row of the cache is read and written:
    it is taken out of the stacked caches, run through the full-sequence
    blocks (MLA expanded per head), and put back. Per-slot states only."""
    caches = state.layer_caches
    nd = cfg.n_dense_layers
    take = lambda a: jax.lax.dynamic_index_in_dim(a, slot, 1, keepdims=True)
    row = A.KVCache(take(caches.k), take(caches.v),
                   jnp.full((cfg.n_layers,), start, jnp.int32))

    def body(h, xs):
        lp, cache = xs
        h, _, new_cache = _block_apply(lp, h, cfg, cache=cache)
        return h, new_cache

    h = L.embed_lookup(params["embed"], tokens[None])
    parts = []
    if nd:
        h, part = jax.lax.scan(body, h, (
            params["dense_layers"], jax.tree.map(lambda a: a[:nd], row)))
        parts.append(part)
    h, part = jax.lax.scan(body, h, (
        params["layers"], jax.tree.map(lambda a: a[nd:], row)))
    parts.append(part)
    new_row = jax.tree.map(lambda *a: jnp.concatenate(a), *parts)
    put = lambda a, r: jax.lax.dynamic_update_index_in_dim(a, r, slot, 1)
    new_pos = state.pos.at[slot].set(start + n)
    new = state._replace(
        pos=new_pos, layer_caches=A.KVCache(
            put(caches.k, new_row.k), put(caches.v, new_row.v),
            jnp.broadcast_to(new_pos, caches.pos.shape)))
    last = jax.lax.dynamic_index_in_dim(h[0], n - 1, 0, keepdims=False)
    return _head(params, cfg, last), new
