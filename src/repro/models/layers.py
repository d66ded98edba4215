"""Shared neural layers: norms, embeddings, RoPE, gated MLP.

Pure-functional convention used across ``repro.models``: each block is an
``init_*(key, ...) -> params-dict`` plus an apply function. Parameters are
stored in the model compute dtype (bf16 by default) except norm scales
(f32); math that needs it (softmax, norm reductions, SSD state) runs in
f32 and casts back.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def rmsnorm_init(d: int):
    return jnp.ones((d,), jnp.float32)


def rmsnorm(scale, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def embed_lookup(table, tokens):
    return jnp.take(table, tokens, axis=0)


# ------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor: 0.1 * mscale * ln(factor) + 1
    (1 for factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, yarn) -> np.ndarray:
    """(D/2,) YaRN rotary frequencies (``configs.base.YarnConfig``): the
    plain frequencies where a dimension turns more than ``beta_fast`` times
    over the original context, divided by ``factor`` where it turns fewer
    than ``beta_slow`` times, and a linear ramp between (deepseek-v2's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    def turns_dim(turns: float) -> float:
        return (head_dim * math.log(yarn.original_max_position
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = rope_freqs(head_dim, theta)
    keep = 1.0 - ramp                     # 1: extrapolate (plain frequency)
    return (extra / yarn.factor * ramp + extra * keep).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs=None, scale: float = 1.0) -> jax.Array:
    """x: (..., S, H, D) with D even; positions: broadcastable to (..., S).
    Rotates the interleaved pairs (x[2i], x[2i+1]) by position * freqs[i]
    (``rope_freqs`` unless ``freqs`` is given); cos and sin are multiplied
    by ``scale`` (YaRN's cos/sin mscale)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta) if freqs is None else freqs)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = scale * jnp.cos(angles)[..., None, :]                # (..., S, 1, D/2)
    sin = scale * jnp.sin(angles)[..., None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def einsum_f32(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """``jnp.einsum`` accumulating in float32 whatever the operands' float
    dtype: bf16 operands stay bf16 on the TPU's matrix unit. XLA's CPU dot
    has no bf16 x bf16 -> f32 form, so when lowered for the CPU the
    operands are widened first (the same products, exactly)."""
    f32 = jnp.float32

    def widened(a, b):
        return jnp.einsum(spec, a.astype(f32), b.astype(f32))

    def native(a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    return jax.lax.platform_dependent(a, b, cpu=widened, default=native)


# --------------------------------------------------------------- gated MLP
def mlp_init(key, d: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, d_ff, dtype),
        "w_up": dense_init(k2, d, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d, dtype),
    }


def mlp_apply(p, x):
    """SwiGLU: down( silu(gate(x)) * up(x) )."""
    g = jax.nn.silu(x @ p["w_gate"])
    return (g * (x @ p["w_up"])) @ p["w_down"]


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None):
    """Mean token CE in f32. logits (..., V), labels (...) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
