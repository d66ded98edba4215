"""Mixture-of-experts layer with Catwalk-style top-k relocation dispatch.

The paper's mechanism at tensor granularity (DESIGN.md §3.4): per token
the router activates k of E experts (k << E, e.g. 2/128 for arctic) — the
same extreme sparsity as spike volleys. Dispatch modes:

  * ``catwalk`` (default): tokens are *relocated* — stably sorted by expert
    id into contiguous per-expert blocks of bounded capacity — so the
    expert FFNs run as dense (E, C, D) batched GEMMs sized by *actual*
    activity (C = T*k/E * capacity_factor), not worst case. The sort is the
    software form of the unary relocation network; capacity overflow drops
    are the exact analogue of the paper's per-cycle clip at k (and are
    equally rare under the router's load-balancing aux loss).
  * ``dense``: every expert processes every token, combined by gate weight
    — the "fully provisioned parallel counter" baseline the paper argues
    against. O(T*E*F) compute; kept for small-scale validation and as the
    paper-baseline in benchmarks.

Experts are sharded expert-parallel (E over 'model'); the relocation
gather/scatter becomes an all-to-all on the mesh (see sharding/specs.py).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels import ops
from repro.models import layers as L


def moe_init(key, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(key, 5)
    e, f = cfg.n_experts, cfg.d_expert
    # experts stacked on axis 0: (E, D, F) / (E, F, D)
    p = {
        "router": L.dense_init(ks[0], d_model, e, jnp.float32),
        "w_gate": _stack_expert(ks[1], e, d_model, f, dtype),
        "w_up": _stack_expert(ks[2], e, d_model, f, dtype),
        "w_down": _stack_expert(ks[3], e, f, d_model, dtype),
    }
    if cfg.n_shared:
        p["shared"] = L.mlp_init(ks[4], d_model, cfg.n_shared * f, dtype)
    return p


def _stack_expert(key, e, d_in, d_out, dtype):
    keys = jax.random.split(key, e)
    return jax.vmap(lambda k: L.dense_init(k, d_in, d_out, dtype))(keys)


def _expert_ffn(p, x):
    """x (E, C, D) -> (E, C, D): per-expert SwiGLU via batched GEMM."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", x, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])


def _aux_loss(probs_full: jax.Array, idx: jax.Array, e: int) -> jax.Array:
    """Switch-style load balancing: E * sum_e f_e * p_e."""
    t = probs_full.shape[0]
    load = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    load = load / jnp.maximum(load.sum(), 1.0)
    importance = jnp.mean(probs_full, axis=0)
    return e * jnp.sum(load * importance)


def moe_apply(p, x: jax.Array, cfg: MoEConfig,
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B, S, D) -> (out, {'aux_loss': scalar})."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    e, k = cfg.n_experts, cfg.top_k

    logits = (xt.astype(jnp.float32) @ p["router"])
    probs_full = jax.nn.softmax(logits, axis=-1)
    probs, idx = ops.moe_gate_topk(logits, k, renorm=True, impl="ref")
    probs = probs.astype(x.dtype)

    if cfg.dispatch == "dense":
        # worst-case baseline: all experts on all tokens
        ys = _expert_ffn(p, jnp.broadcast_to(xt, (e, t, d)))     # (E,T,D)
        gate = jnp.zeros((t, e), x.dtype)
        gate = gate.at[jnp.arange(t)[:, None], idx].set(probs)
        out = jnp.einsum("te,etd->td", gate, ys)
    else:
        # ---- Catwalk relocation dispatch --------------------------------
        # Gather-only formulation: all LARGE tensor movement is expressed
        # as takes (SPMD-partitionable); scatters touch only small int32
        # index tables. floor of k slots/expert keeps tiny-T (decode)
        # paths drop-free.
        from repro.sharding.specs import dp_spec_names, maybe_wsc
        dp = dp_spec_names()
        cap = min(t, max(k, int(t * k / e * cfg.capacity_factor)))
        flat_e = idx.reshape(-1)                                 # (T*k,)
        flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
        order = jnp.argsort(flat_e, stable=True)                 # relocate
        sorted_e = flat_e[order]
        # rank within expert segment = global sorted pos - segment start
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
        rank = jnp.arange(t * k, dtype=jnp.int32) - seg_start[sorted_e]
        keep = rank < cap                                        # clip at C
        slot = jnp.where(keep, sorted_e * cap + rank, e * cap)   # overflow
        # slot -> source-token table (int32, E*cap+1 entries, cheap)
        slot_src = jnp.full((e * cap + 1,), t, jnp.int32)
        slot_src = slot_src.at[slot].set(
            jnp.where(keep, flat_tok[order], t))
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), x.dtype)], 0)
        expert_in = jnp.take(xt_pad, slot_src[:-1], axis=0
                             ).reshape(e, cap, d)
        expert_in = maybe_wsc(expert_in, "model", None, None)    # EP
        expert_out = _expert_ffn(p, expert_in)
        expert_out = maybe_wsc(expert_out, "model", None, None)
        eo_flat = jnp.concatenate(
            [expert_out.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], 0)
        # per-assignment slot in TOKEN order (inverse relocation)
        inv = jnp.argsort(order)
        token_slot = jnp.where(keep, slot, e * cap)[inv]         # (T*k,)
        contrib = jnp.take(eo_flat, token_slot, axis=0
                           ).reshape(t, k, d)
        contrib = maybe_wsc(contrib, dp, None, None)
        out = jnp.sum(contrib * probs[..., None], axis=1)

    if cfg.n_shared:
        out = out + L.mlp_apply(p["shared"], xt)
    aux = cfg.router_aux_loss * _aux_loss(probs_full, idx, e)
    return out.reshape(b, s, d), {"aux_loss": aux}


# ---------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map (§Perf hillclimb, --opt layout).
#
# Layout: tokens P(dp, None, None) — replicated over 'model'; experts
# E over 'model'. Every (data, model) chip routes its LOCAL tokens, keeps
# only assignments to its OWN E_loc experts (the Catwalk relocation,
# applied per owner), runs the dense (E_loc, C, D) FFN, scatters partial
# outputs back to token rows, and a single psum over 'model' combines the
# k expert contributions. Per-layer collective traffic: ONE activation
# all-reduce over the 16-way model axis (+ optional FSDP weight gathers),
# vs auto-SPMD's replicated-activation all-reduce + 5x redundant gathers
# (measured: 32 GB -> ~0.8 GB per layer per chip on deepseek-v2-lite).
# ---------------------------------------------------------------------------


def _local_dispatch_ffn(p_loc, xt, cfg: MoEConfig, e_lo, e_loc):
    """Per-shard body: xt (T_loc, D) local tokens; p_loc holds E_loc
    experts (already gathered to full F)."""
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = xt.astype(jnp.float32) @ p_loc["router"]
    probs_full = jax.nn.softmax(logits, axis=-1)
    probs, idx = ops.moe_gate_topk(logits, k, renorm=True, impl="ref")
    probs = probs.astype(xt.dtype)

    mine = (idx >= e_lo) & (idx < e_lo + e_loc)             # (T, k)
    local_e = jnp.where(mine, idx - e_lo, e_loc)            # e_loc = trash
    cap = min(t, max(k, int(t * k / e * cfg.capacity_factor)))
    flat_e = local_e.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)                # relocation
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e_loc + 1),
                                 side="left")
    rank = jnp.arange(t * k, dtype=jnp.int32) - seg_start[sorted_e]
    keep = (sorted_e < e_loc) & (rank < cap)
    slot = jnp.where(keep, sorted_e * cap + rank, e_loc * cap)
    slot_src = jnp.full((e_loc * cap + 1,), t, jnp.int32)
    slot_src = slot_src.at[slot].set(jnp.where(keep, flat_tok[order], t))
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)
    expert_in = jnp.take(xt_pad, slot_src[:-1], axis=0).reshape(
        e_loc, cap, d)
    expert_out = _expert_ffn(p_loc, expert_in).reshape(e_loc * cap, d)
    eo_flat = jnp.concatenate(
        [expert_out, jnp.zeros((1, d), xt.dtype)], 0)
    inv = jnp.argsort(order)
    token_slot = jnp.where(keep, slot, e_loc * cap)[inv]
    contrib = jnp.take(eo_flat, token_slot, axis=0).reshape(t, k, d)
    out_partial = jnp.sum(contrib * probs[..., None], axis=1)
    if cfg.n_shared:
        # shared experts run tensor-parallel over 'model' (F_loc shards);
        # their partial sums ride the same psum as the routed combine
        out_partial = out_partial + L.mlp_apply(p_loc["shared"], xt)
    # ONE all-reduce combines routed + shared contributions across owners
    out = jax.lax.psum(out_partial, "model")
    aux = cfg.router_aux_loss * _aux_loss(probs_full, idx, e)
    return out, aux


def moe_apply_ep(p, x: jax.Array, cfg: MoEConfig, fsdp: bool = False
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """shard_map expert-parallel MoE; requires an active mesh with a
    'model' axis dividing n_experts. Falls back to moe_apply otherwise.

    ``fsdp``: expert F dims stay sharded over the DP axes at rest and are
    all-gathered per use (arctic-scale experts don't fit replicated)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import compat
    am = compat.get_abstract_mesh()
    names = set(am.axis_names)
    if "model" not in names or cfg.n_experts % am.shape["model"]:
        return moe_apply(p, x, cfg)
    m_size = am.shape["model"]
    e_loc = cfg.n_experts // m_size
    dp = tuple(a for a in ("pod", "data") if a in names)
    dpspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    fsdp = fsdp and bool(dp)

    b, s, d = x.shape
    xt = x.reshape(b * s, d)

    wspec_in = P("model", None, dpspec if fsdp else None)
    wspec_out = P("model", dpspec if fsdp else None, None)
    shared_ok = cfg.n_shared and \
        (cfg.n_shared * cfg.d_expert) % m_size == 0
    in_specs = (
        {
            "router": P(None, None),
            "w_gate": wspec_in,
            "w_up": wspec_in,
            "w_down": wspec_out,
            **({"shared": {"w_gate": P(None, "model"),
                           "w_up": P(None, "model"),
                           "w_down": P("model", None)}}
               if shared_ok else {}),
        },
        P(dpspec, None),
    )
    if cfg.n_shared and not shared_ok:
        return moe_apply(p, x, cfg)     # tiny-smoke fallback

    def body(p_loc, xt_loc):
        if fsdp:
            from jax.ad_checkpoint import checkpoint_name
            # tag gathered weights: the block remat policy saves them, so
            # the backward pass reuses instead of re-gathering (§Perf H7)
            p_loc = dict(
                p_loc,
                w_gate=checkpoint_name(
                    jax.lax.all_gather(p_loc["w_gate"], dp, axis=2,
                                       tiled=True), "moe_gathered"),
                w_up=checkpoint_name(
                    jax.lax.all_gather(p_loc["w_up"], dp, axis=2,
                                       tiled=True), "moe_gathered"),
                w_down=checkpoint_name(
                    jax.lax.all_gather(p_loc["w_down"], dp, axis=1,
                                       tiled=True), "moe_gathered"),
            )
        e_lo = jax.lax.axis_index("model") * e_loc
        out, aux = _local_dispatch_ffn(p_loc, xt_loc, cfg, e_lo, e_loc)
        return out, jax.lax.pmean(aux, dp + ("model",))

    p_in = {k: p[k] for k in in_specs[0]}
    out, aux = compat.shard_map(
        body, mesh=am, in_specs=in_specs,
        out_specs=(P(dpspec, None), P()))(p_in, xt)
    return out.reshape(b, s, d), {"aux_loss": aux}
