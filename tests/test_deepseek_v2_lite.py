"""DeepSeek-V2-Lite's mechanisms against the plain float32 reference
(``models/reference.py``), at a smoke size on the CPU on seeded random
weights: chunked prefill then absorbed-MLA decode through the engine's
``submit``/``step``, absorbed against expanded attention, YaRN, and the
drop-free expert layer that holds a share of the experts."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import reference as R
from repro.models import transformer as T
from repro.serve import Engine, LMRequest, ServeConfig


def _smoke(dtype="bfloat16"):
    return dataclasses.replace(get_config("deepseek-v2-lite-16b").smoke(),
                               dtype=dtype)


def _serve(cfg, params, lengths):
    """Requests (prompt, tokens to generate) through submit/step on 3
    slots, prefilling 5 prompt tokens a step (so prompts span chunks)."""
    eng = Engine(params, cfg, ServeConfig(
        max_len=40, n_slots=3, prefill_tokens_per_step=5, ignore_eos=True))
    rng = np.random.default_rng(0)
    reqs = [eng.submit(LMRequest(prompt=rng.integers(0, cfg.vocab_size, n),
                                 max_new_tokens=g)) for n, g in lengths]
    while eng.has_work:
        eng.step()
    return reqs


def _errors(cfg, params, req):
    """Per generated token, in units of the reference row's standard
    deviation: |logit - reference logit| and |lse - reference lse|, from
    the reference's full forward over the prompt and generated tokens."""
    out = req.result()
    ids = out[:, 0].astype(np.int32)
    seq = np.concatenate([req.prompt, ids[:-1]])
    ref = np.asarray(R.forward(params, cfg, seq))[req.prompt.size - 1:]
    std = ref.std(-1)
    lse = np.asarray(jax.nn.logsumexp(ref, -1))
    return (np.abs(out[:, 1] - ref[np.arange(len(ids)), ids]) / std,
            np.abs(out[:, 2] - lse) / std)


LENGTHS = [(7, 9), (3, 12), (12, 5), (9, 8)]


def test_prefill_then_decode_matches_reference_in_float32():
    """In float32 the served path computes the reference's mathematics:
    only the order of sums differs (absorbed against expanded attention,
    chunked against whole prefill, relocated against dense experts), so
    logits agree to float32 rounding, 1e-4 of a row's spread."""
    cfg = _smoke("float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _serve(cfg, params, LENGTHS)
    for r in reqs:
        assert len(r.tokens) == r.max_new_tokens
        assert r.result().shape == (r.max_new_tokens, 3)
        logit_err, lse_err = _errors(cfg, params, r)
        assert logit_err.max() < 1e-4 and lse_err.max() < 1e-4


def test_prefill_then_decode_matches_reference_in_bfloat16():
    """In bf16 (the configuration's dtype) activations round at every
    layer, and a router near-tie can pick another expert than the float32
    reference: the worst logit seen here is 0.12 of a row's spread, the
    log-sum-exp 0.005. Bounds 0.25 and 0.02 leave twice that room; the
    float8 control of the benchmark reads 0.6 and more."""
    cfg = _smoke()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    for r in _serve(cfg, params, LENGTHS):
        logit_err, lse_err = _errors(cfg, params, r)
        assert logit_err.max() < 0.25 and lse_err.max() < 0.02


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.05)])
def test_absorbed_matches_expanded_attention(dtype, tol):
    """Absorbing w_ukv into the query and output changes only the order of
    products: exact to float32 rounding in float32; in bf16 the absorbed
    query and the probabilities round to bf16 (a few 1e-3 relative)."""
    cfg = _smoke(dtype)
    dt = jnp.dtype(dtype)
    p = A.mla_init(jax.random.PRNGKey(1), cfg, dt)
    b, s, t = 3, 2, 11
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.d_model), dt)
    keys = jax.random.normal(jax.random.PRNGKey(3), (b, t, cfg.d_model), dt)
    pos = jnp.array([[4, 5], [9, 10], [0, 1]], jnp.int32)
    q_nope, q_rope, _, _ = A.mla_project(p, x, cfg, pos)
    _, _, c_kv, k_rope = A.mla_project(
        p, keys, cfg, jnp.broadcast_to(jnp.arange(t), (b, t)))
    want = A.mla_attend(p, q_nope, q_rope, c_kv, k_rope, pos, cfg)
    got = A.mla_attend_absorbed(p, q_nope, q_rope, c_kv, k_rope, pos, cfg)
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol * scale, rtol=0)


def test_yarn_frequencies_and_scale_by_hand():
    """The published YaRN (factor 40 over 4096 positions, beta 32 / 1) on
    the 64 rotary dims of theta 10000: pairs below 10 keep their
    frequency, pairs from 23 on are divided by 40, a linear ramp between
    (the correction range: floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4))
    = 10 and ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23); mscale =
    0.1 * 0.707 * ln 40 + 1 = 1.26080, so the softmax scale is 192^-0.5 x
    mscale^2 and cos/sin keep scale 1 (mscale = mscale_all_dim)."""
    cfg = get_config("deepseek-v2-lite-16b")
    freqs, cs, scale = A.mla_rope(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(freqs, plain * (1 - ramp) + plain / 40 * ramp,
                               rtol=1e-6)
    assert freqs[9] == pytest.approx(plain[9], rel=1e-6)
    assert freqs[23] == pytest.approx(plain[23] / 40, rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert scale == pytest.approx(m * m / math.sqrt(192), rel=1e-6)
    assert cs == 1.0
    assert L.yarn_mscale(1.0, 0.707) == 1.0
    inv, cs_r, scale_r = R.rope_tables(cfg)       # the reference agrees
    np.testing.assert_allclose(inv, freqs, rtol=1e-6)
    assert (cs_r, scale_r) == pytest.approx((cs, scale), rel=1e-9)


def _moe_cfg(**kw):
    base = dict(n_experts=16, top_k=3, d_expert=24, n_shared=2,
                norm_topk=False)
    base.update(kw)
    return MoEConfig(**base)


def _ref_layer(p, x, mc, e_lo=0):
    cfg = dataclasses.replace(_smoke("float32"), moe=mc)
    with jax.default_matmul_precision("highest"):
        return R._moe(p, x, cfg, e_lo)


def test_drop_free_layer_under_routing_onto_one_expert():
    """Every token routed to expert 0 (and two more): a capacity-bounded
    layer drops most of them; this one computes every assignment, so it
    matches the reference, which computes every expert densely."""
    mc = _moe_cfg()
    d = 32
    p = M.moe_init(jax.random.PRNGKey(4), d, mc, jnp.float32)
    p["router"] = p["router"].at[:, 0].add(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (64, d)))
    with jax.default_matmul_precision("highest"):
        out, _ = M.held_experts(p, x, mc, 0, mc.n_experts)
    _, idx = jax.lax.top_k(x @ p["router"], mc.top_k)
    assert bool(jnp.all(idx[:, 0] == 0))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref_layer(p, x, mc)),
                               rtol=1e-5, atol=1e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips of an expert-parallel deployment, each holding 2 of the
    16 experts and routing over all of them: their partial outputs, with
    the shared experts (which every chip computes alike) counted once, add
    up to the uncut reference layer; each share also matches the
    reference given the same share."""
    mc = _moe_cfg()
    d = 32
    p = M.moe_init(jax.random.PRNGKey(6), d, mc, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (40, d))
    shared = L.mlp_apply(p["shared"], x)
    total = jnp.zeros_like(x)
    for chip in range(8):
        lo = 2 * chip
        share = dataclasses.replace(mc, held=(lo, 2))
        p_loc = dict(p, **{k: p[k][lo:lo + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        with jax.default_matmul_precision("highest"):
            part, _ = M.moe_apply(p_loc, x[None], share)
        np.testing.assert_allclose(
            np.asarray(part[0]), np.asarray(_ref_layer(p_loc, x, share, lo)),
            rtol=1e-5, atol=1e-5)
        total = total + part[0] - shared
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(_ref_layer(p, x, mc)),
                               rtol=1e-5, atol=2e-5)
