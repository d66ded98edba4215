"""Mixture-of-experts layer with Catwalk-style top-k relocation dispatch.

The paper's mechanism at tensor granularity (DESIGN.md §3.4): per token
the router activates k of E experts (k << E, e.g. 2/128 for arctic) — the
same extreme sparsity as spike volleys. Tokens are *relocated*: their
(token, expert) assignments are stably sorted by expert id into contiguous
per-expert runs, and the expert FFNs run as one grouped (ragged) product
sized by the actual activity. The sort is the software form of the unary
relocation network.

One body serves every layout (``held_experts``). A layer is told which
experts it holds, ``[e_lo, e_lo + e_held)`` (``MoEConfig.held``; all of them
by default): it routes every token over all ``n_experts`` with the published
top-k, computes its own experts' part with no capacity limit (so it drops no
assignment) and adds the shared experts. On one chip that is the whole layer, or the chip's share of an
expert-parallel deployment; under ``shard_map`` (``moe_apply_ep``) each
shard runs it for its slice of the experts and a psum combines them.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels import ops
from repro.models import layers as L


def held_range(cfg: MoEConfig) -> Tuple[int, int]:
    """(e_lo, e_held): the experts this layer holds."""
    return cfg.held if cfg.held is not None else (0, cfg.n_experts)


def moe_init(key, d_model: int, cfg: MoEConfig, dtype):
    """Router over all ``n_experts``; stacked weights for the held ones."""
    ks = jax.random.split(key, 5)
    e, f = held_range(cfg)[1], cfg.d_expert
    # held experts stacked on axis 0: (E_held, D, F) / (E_held, F, D)
    p = {
        "router": L.dense_init(ks[0], d_model, cfg.n_experts, jnp.float32),
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


def _aux_loss(probs_full: jax.Array, idx: jax.Array, e: int) -> jax.Array:
    """Switch-style load balancing: E * sum_e f_e * p_e."""
    load = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    load = load / jnp.maximum(load.sum(), 1.0)
    importance = jnp.mean(probs_full, axis=0)
    return e * jnp.sum(load * importance)


def held_experts(p, xt: jax.Array, cfg: MoEConfig, e_lo, e_held: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """The layer's part from experts ``[e_lo, e_lo + e_held)`` plus the
    shared experts, for tokens ``xt`` (T, D).

    Returns (out (T, D), aux loss). ``p`` holds the ``e_held`` experts'
    weights."""
    t, d = xt.shape
    k = cfg.top_k
    logits = xt.astype(jnp.float32) @ p["router"]              # all experts
    probs_full = jax.nn.softmax(logits, axis=-1)
    gates, idx = ops.moe_gate_topk(logits, k, renorm=cfg.norm_topk,
                                   impl="ref")
    mine = (idx >= e_lo) & (idx < e_lo + e_held)               # (T, k)
    flat = jnp.where(mine, idx - e_lo, e_held).reshape(-1)     # e_held: absent
    # relocation: assignments sorted by held expert, the absent ones last
    order = jnp.argsort(flat, stable=True)
    rows = jnp.take(xt, order // k, axis=0)                    # (T*k, D)
    sizes = jnp.zeros((e_held + 1,), jnp.int32).at[flat].add(1)[:e_held]
    g = jax.nn.silu(jax.lax.ragged_dot(rows, p["w_gate"], sizes))
    u = jax.lax.ragged_dot(rows, p["w_up"], sizes)
    y = jax.lax.ragged_dot((g * u).astype(xt.dtype), p["w_down"], sizes)
    # inverse relocation (a gather): each token's k rows, in token order;
    # rows past sum(sizes) are zero, and their gates are masked anyway
    contrib = jnp.take(y, jnp.argsort(order), axis=0).reshape(t, k, d)
    w = jnp.where(mine, gates, 0.0).astype(jnp.float32)
    out = jnp.einsum("tkd,tk->td", contrib.astype(jnp.float32), w)
    if cfg.n_shared:
        out = out + L.mlp_apply(p["shared"], xt).astype(jnp.float32)
    aux = cfg.router_aux_loss * _aux_loss(probs_full, idx, cfg.n_experts)
    return out.astype(xt.dtype), aux


def moe_apply(p, x: jax.Array, cfg: MoEConfig,
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B, S, D) -> (out, {'aux_loss': scalar})."""
    b, s, d = x.shape
    e_lo, e_held = held_range(cfg)
    out, aux = held_experts(p, x.reshape(b * s, d), cfg, e_lo, e_held)
    return out.reshape(b, s, d), {"aux_loss": aux}


# ---------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map (§Perf hillclimb, --opt layout).
#
# Layout: tokens P(dp, None, None) — replicated over 'model'; experts
# E over 'model'. Every (data, model) chip routes its LOCAL tokens, keeps
# only assignments to its OWN E_loc experts (the Catwalk relocation,
# applied per owner), runs the grouped FFN, and a single psum over 'model'
# combines the k expert contributions. Per-layer collective traffic: ONE
# activation all-reduce over the 16-way model axis (+ optional FSDP weight
# gathers), vs auto-SPMD's replicated-activation all-reduce + 5x redundant
# gathers (measured: 32 GB -> ~0.8 GB per layer per chip on
# deepseek-v2-lite).
# ---------------------------------------------------------------------------


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
    if ("model" not in names or cfg.held is not None
            or cfg.n_experts % am.shape["model"]):
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
        # shared experts run tensor-parallel over 'model' (F_loc shards);
        # their partial sums ride the same psum as the routed combine
        out, aux = held_experts(p_loc, xt_loc, cfg, e_lo, e_loc)
        # ONE all-reduce combines routed + shared contributions across owners
        return (jax.lax.psum(out, "model"),
                jax.lax.pmean(aux, dp + ("model",)))

    p_in = {k: p[k] for k in in_specs[0]}
    out, aux = compat.shard_map(
        body, mesh=am, in_specs=in_specs,
        out_specs=(P(dpspec, None), P()))(p_in, xt)
    return out.reshape(b, s, d), {"aux_loss": aux}
