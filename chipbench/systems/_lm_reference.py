"""Plain float32 DeepSeek-V2-Lite forward, the benchmark's own copy.

It imports nothing of the program (as ``harness/reference.py`` does not): it
reads the configuration file's keys and the seed's weights, which it makes
itself (``init_weights``) in the program's parameter tree and the runner
fetches to the host. Every product runs in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 product
otherwise runs in bf16), from the weights widened exactly.

The mathematics, per position (``src/repro/models/reference.py`` is the
program's copy, with the same departures from the HF modelling code):

  * the ``first_k_dense_replace`` leading layers: SwiGLU of
    ``intermediate_size``; the others: the router over all
    ``n_routed_experts_published`` experts, softmax scores, the top
    ``num_experts_per_tok`` (renormalised only when ``norm_topk_prob``),
    every expert held here (``e_lo`` onward, ``n_routed_experts`` of them)
    computed densely and weighted by its gate (0 when not chosen), plus the
    ``n_shared_experts`` shared ones;
  * latent attention expanded per head: c_kv after its RMSNorm
    (``kv_a_layernorm``), keys and values up-projected per head, YaRN rope
    (``rope_scaling``: frequencies, cos/sin scale, softmax scale times
    mscale^2) on the interleaved pairs of the rotary parts, causal softmax.

A long sequence is computed in blocks so that it fits: ``Reference.run``
takes a block of tokens at a start position together with the latent c_kv
and rotary keys of every earlier position (which a causal forward computes
the same whatever follows them), so the blocks together give exactly the
full forward. A document's blocks are computed once and shared by the
questions that read it.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(config: Dict):
    """(inv_freq (d_rope/2,), cos/sin scale, softmax scale) from the
    configuration's ``rope_theta`` and ``rope_scaling`` (YaRN)."""
    d, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    scale = (int(config["qk_nope_head_dim"]) + d) ** -0.5
    y = config.get("rope_scaling")
    if not y:
        return inv.astype(np.float32), 1.0, scale
    assert y["type"] == "yarn", y
    f = float(y["factor"])

    def dim(rot):
        return (d * math.log(y["original_max_position_embeddings"]
                             / (rot * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(dim(y["beta_fast"])), 0)
    high = min(math.ceil(dim(y["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    inv = inv / f * (1 - mask) + inv * mask
    cs = _yarn_mscale(f, y["mscale"]) / _yarn_mscale(f, y["mscale_all_dim"])
    if y["mscale_all_dim"]:
        scale *= _yarn_mscale(f, y["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), cs, scale


def init_weights(key, config: Dict):
    """The seed's weights, in the layout of the program's parameter tree
    (the one thing the two share): every matrix normal with variance
    1/fan-in (the embedding's 0.02), in the configuration's ``dtype``, the
    router in float32; every norm scale 1. Shapes from the configuration's
    keys: the ``first_k_dense_replace`` dense layers under
    ``dense_layers``, the others stacked under ``layers``, the held experts
    stacked on the axis after the layer's."""
    c = config
    if c["tie_word_embeddings"]:
        raise ValueError("reference: tied embeddings are not computed here")
    dt = jnp.dtype(c["dtype"])
    d, v = int(c["hidden_size"]), int(c["vocab_size"])
    h, r = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
    dn, dr = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    dv, f = int(c["v_head_dim"]), int(c["moe_intermediate_size"])
    nd = int(c["first_k_dense_replace"])
    nm = int(c["num_hidden_layers"]) - nd
    keys = iter(jax.random.split(key, 32))

    def mat(lead, d_in, d_out, dtype=dt, scale=None):
        s = d_in ** -0.5 if scale is None else scale
        return (jax.random.normal(next(keys), lead + (d_in, d_out), F32)
                * s).astype(dtype)

    def swiglu(lead, width):
        return {"w_gate": mat(lead, d, width), "w_up": mat(lead, d, width),
                "w_down": mat(lead, width, d)}

    def layers(n, ffn):
        return {"norm1": jnp.ones((n, d), F32), "norm2": jnp.ones((n, d), F32),
                "attn": {"wq": mat((n,), d, h * (dn + dr)),
                         "w_dkv": mat((n,), d, r),
                         "kv_norm": jnp.ones((n, r), F32),
                         "w_kr": mat((n,), d, dr),
                         "w_ukv": mat((n,), r, h * (dn + dv)),
                         "wo": mat((n,), h * dv, d)}, **ffn}

    moe = {"router": mat((nm,), d, int(c["n_routed_experts_published"]),
                         F32),
           **swiglu((nm, int(c["n_routed_experts"])), f)}
    if int(c["n_shared_experts"]):
        moe["shared"] = swiglu((nm,), int(c["n_shared_experts"]) * f)
    params = {"embed": mat((), v, d, scale=0.02),
              "final_norm": jnp.ones((d,), F32), "lm_head": mat((), d, v),
              "layers": layers(nm, {"moe": moe})}
    if nd:
        params["dense_layers"] = layers(nd, {"mlp": swiglu(
            (nd,), int(c["intermediate_size"]))})
    return params


def _w(a):
    return a.astype(F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _w(w)


def _rope(x, pos, inv, cs):
    """x (S, ..., D): rotate pairs (x[2i], x[2i+1]) by pos * inv[i]."""
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = cs * jnp.cos(ang), cs * jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _swiglu(p, x):
    return (jax.nn.silu(x @ _w(p["w_gate"])) * (x @ _w(p["w_up"]))) \
        @ _w(p["w_down"])


class Reference:
    """The forward on the device, from the host's parameter tree."""

    def __init__(self, params_host, config: Dict):
        c = config
        for key, want in (("q_lora_rank", None), ("scoring_func", "softmax"),
                          ("topk_method", "greedy"), ("n_group", 1),
                          ("routed_scaling_factor", 1), ("moe_layer_freq", 1)):
            if c.get(key, want) != want:
                raise ValueError(f"reference: {key} = {c[key]!r} is not "
                                 "computed here")
        self.dims = dict(
            h=int(c["num_attention_heads"]), dn=int(c["qk_nope_head_dim"]),
            dr=int(c["qk_rope_head_dim"]), dv=int(c["v_head_dim"]),
            r=int(c["kv_lora_rank"]), k=int(c["num_experts_per_tok"]),
            e_lo=int(c["e_lo"]), norm_topk=bool(c["norm_topk_prob"]),
            eps=float(c["rms_norm_eps"]))
        self.max_len = int(c["max_len"])
        self.n_layers = int(c["num_hidden_layers"])
        inv, cs, scale = rope_tables(c)
        self.rope = (jnp.asarray(inv), float(cs), float(scale))
        self.params = jax.device_put(params_host)
        self._run = jax.jit(partial(_block, dims=self.dims, rope=self.rope))

    def empty(self):
        """Latent and rotary keys of no earlier position: (L, S, R), (L, S,
        d_rope) float32."""
        d = self.dims
        return (jnp.zeros((self.n_layers, self.max_len, d["r"]), F32),
                jnp.zeros((self.n_layers, self.max_len, d["dr"]), F32))

    def run(self, kv, tokens: np.ndarray, start: int, length: int,
            targets: np.ndarray):
        """One block: ``tokens`` (padded to its compiled length; the first
        ``length`` real) at positions ``start`` onward, after the positions
        whose keys ``kv`` holds. Returns (kv with this block's keys, (Bk, 4)
        float32 per position: logit at ``targets``, log-sum-exp, largest
        logit, standard deviation of the row)."""
        with jax.default_matmul_precision("highest"):
            return self._run(self.params, kv, jnp.asarray(tokens, jnp.int32),
                             jnp.int32(start), jnp.int32(length),
                             jnp.asarray(targets, jnp.int32))


def _attention(p, x, kv_c, kv_r, pos, dims, rope):
    """x (S, D) at positions ``pos``; kv_c/kv_r (T, ·) the keys of this
    layer after this block's are written."""
    h, dn, dr, dv = dims["h"], dims["dn"], dims["dr"], dims["dv"]
    inv, cs, scale = rope
    s = x.shape[0]
    q = (x @ _w(p["wq"])).reshape(s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, inv, cs)
    kv = (kv_c @ _w(p["w_ukv"])).reshape(kv_c.shape[0], h, dn + dv)
    scores = (jnp.einsum("shd,thd->hst", q_nope, kv[..., :dn])
              + jnp.einsum("shd,td->hst", q_pe, kv_r)) * scale
    causal = jnp.arange(kv_c.shape[0])[None, None, :] <= pos[None, :, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("hst,thd->shd", probs, kv[..., dn:]).reshape(s, -1)
    return out @ _w(p["wo"])


def _keys(p, x, pos, dims, rope):
    """This block's latent c_kv (after its norm) and rotary keys."""
    inv, cs, _ = rope
    c = _rms(x @ _w(p["w_dkv"]), p["kv_norm"], dims["eps"])
    return c, _rope(x @ _w(p["w_kr"]), pos, inv, cs)


def _moe(p, x, dims):
    probs = jax.nn.softmax(x @ _w(p["router"]), -1)          # all experts
    top, idx = jax.lax.top_k(probs, dims["k"])
    if dims["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)
    held = jax.lax.dynamic_slice_in_dim(gates, dims["e_lo"],
                                        p["w_gate"].shape[0], 1)

    def expert(out, xs):
        w_gate, w_up, w_down, g = xs
        y = _swiglu({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, x)
        return out + g[:, None] * y, None

    out, _ = jax.lax.scan(expert, _swiglu(p["shared"], x)
                          if "shared" in p else jnp.zeros_like(x),
                          (p["w_gate"], p["w_up"], p["w_down"], held.T))
    return out


def _layer(x, p, kv_c, kv_r, pos, dims, rope, moe: bool):
    hn = _rms(x, p["norm1"], dims["eps"])
    c, r = _keys(p["attn"], hn, pos, dims, rope)
    kv_c = kv_c.at[pos].set(c, mode="drop")
    kv_r = kv_r.at[pos].set(r, mode="drop")
    x = x + _attention(p["attn"], hn, kv_c, kv_r, pos, dims, rope)
    hn = _rms(x, p["norm2"], dims["eps"])
    return x + (_moe(p["moe"], hn, dims) if moe else
                _swiglu(p["mlp"], hn)), kv_c, kv_r


def _block(params, kv, tokens, start, length, targets, *, dims, rope):
    kv_c, kv_r = kv
    pos = start + jnp.arange(tokens.shape[0])
    # padding positions past the block's real ones write no key
    pos_w = jnp.where(jnp.arange(tokens.shape[0]) < length, pos,
                      kv_c.shape[1])
    x = _w(params["embed"])[tokens]
    nd = 0
    if "dense_layers" in params:
        nd = jax.tree.leaves(params["dense_layers"])[0].shape[0]
        for i in range(nd):
            p = jax.tree.map(lambda a: a[i], params["dense_layers"])
            x, c, r = _layer(x, p, kv_c[i], kv_r[i], pos, dims, rope, False)
            kv_c, kv_r = kv_c.at[i].set(_keep(c, kv_c[i], pos_w, pos)), \
                kv_r.at[i].set(_keep(r, kv_r[i], pos_w, pos))

    def body(x, xs):
        p, c_l, r_l = xs
        x, c, r = _layer(x, p, c_l, r_l, pos, dims, rope, True)
        return x, (_keep(c, c_l, pos_w, pos), _keep(r, r_l, pos_w, pos))

    x, (c_moe, r_moe) = jax.lax.scan(body, x, (params["layers"], kv_c[nd:],
                                               kv_r[nd:]))
    kv_c = jnp.concatenate([kv_c[:nd], c_moe])
    kv_r = jnp.concatenate([kv_r[:nd], r_moe])
    x = _rms(x, params["final_norm"], dims["eps"])
    logits = x @ _w(params["lm_head"])                       # (Bk, V)
    stats = jnp.stack([
        jnp.take_along_axis(logits, targets[:, None], -1)[:, 0],
        jax.nn.logsumexp(logits, -1), jnp.max(logits, -1),
        jnp.std(logits, -1)], -1)
    return (kv_c, kv_r), stats


def _keep(new, old, pos_w, pos):
    """The layer's keys with this block's real positions from ``new`` and
    every other position as it was (padding writes nothing)."""
    return old.at[pos_w].set(new[pos], mode="drop")
