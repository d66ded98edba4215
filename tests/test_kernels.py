"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

All kernels run in interpret mode on CPU (the TPU lowering is exercised by
the dry-run's ShapeDtypeStruct compilation path via the ref impl)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.topk_prune import topk_network
from repro.kernels import ops, ref


# ---------------------------------------------------------------- unary_topk
@pytest.mark.parametrize("n,k,kind", [(8, 2, "optimal"), (16, 2, "auto"),
                                      (16, 4, "bitonic"), (32, 2, "auto"),
                                      (64, 2, "auto"), (64, 4, "selection")])
@pytest.mark.parametrize("density", [0.02, 0.3])
def test_unary_topk_matches_oracle(n, k, kind, density):
    net = topk_network(kind, n, k)
    bits = jax.random.bernoulli(jax.random.PRNGKey(n * k), density,
                                (17, 9, n))
    got = ops.unary_topk_relocate(bits, net, impl="pallas")
    want = ref.unary_topk_relocate(bits, net)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=25, deadline=None)
@given(rows=st.integers(1, 70), seed=st.integers(0, 2**31 - 1))
def test_unary_topk_property_counts(rows, seed):
    """sum(out) == min(popcount, k) for arbitrary row counts (padding)."""
    net = topk_network("auto", 16, 2)
    bits = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.2, (rows, 16))
    cnt = ops.unary_topk_count(bits, net, impl="pallas")
    pc = jnp.sum(bits.astype(jnp.int32), axis=-1)
    np.testing.assert_array_equal(np.asarray(cnt),
                                  np.asarray(jnp.minimum(pc, 2)))


# ---------------------------------------------------------------- rnl_neuron
@pytest.mark.parametrize("bsz,q,n", [(1, 1, 8), (13, 5, 16), (32, 24, 64),
                                      (4, 200, 16)])
@pytest.mark.parametrize("k", [None, 2, 4])
def test_rnl_matches_oracle(bsz, q, n, k):
    kt, kw = jax.random.split(jax.random.PRNGKey(bsz * n))
    times = jax.random.randint(kt, (bsz, n), 0, 40)
    w = jax.random.randint(kw, (q, n), 0, 8)
    got = ops.rnl_fire_times(times, w, t_steps=48, threshold=9, k=k,
                             impl="pallas")
    want = ref.rnl_fire_times(times, w, t_steps=48, threshold=9, k=k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rnl_agrees_with_core_neuron():
    """Kernel == repro.core.neuron closed forms (cross-module contract)."""
    from repro.core import neuron
    times = jax.random.randint(jax.random.PRNGKey(0), (6, 16), 0, 30)
    w = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 8)
    got = ops.rnl_fire_times(times, w, t_steps=40, threshold=7, k=2,
                             impl="pallas")
    for qi in range(3):
        want = neuron.fire_time_catwalk_closed_form(times, w[qi], 7, 40, 2)
        np.testing.assert_array_equal(np.asarray(got[:, qi]),
                                      np.asarray(want))


@pytest.mark.parametrize("bsz", [1, 32, 130])
@pytest.mark.parametrize("n,q,threshold", [(32, 12, 15), (12, 10, 4),
                                           (256, 16, 40), (64, 200, 20),
                                           (12, 10, 0)])
@pytest.mark.parametrize("k", [None, 2])
def test_layer_kernel_matches_engines(bsz, n, q, threshold, k):
    """The lane-dense layer kernel == the jnp closed-form and scan engines,
    fire times and clip counts, across its tiling edges: slots padded to
    128 lanes (B=130 spans two lane tiles), 7 columns over column blocks
    (n=256 and Q=200 pad them with silent columns), Q > 128 over neuron
    tiles, threshold <= 0 (fires at tick 0 on silence)."""
    _check_layer_kernel(*_random_layer(7, bsz, n, q), threshold, k)


def test_layer_kernel_bounds_each_column_block():
    """Each column block stops at its own last breakpoint: the first block's
    ramps end by tick 2, the others' start at tick 8."""
    from repro.kernels import rnl_neuron
    assert rnl_neuron._layer_tiling(7, 256, 16, 2) == (3, 3)
    times, w = _random_layer(7, 9, 256, 16)
    times = times.at[:3].set(jnp.minimum(times[:3], 1))
    w = w.at[:3].set(jnp.minimum(w[:3], 1))
    times = times.at[3:].set(jnp.clip(times[3:], 8, 15))
    _check_layer_kernel(times, w, 8, 2)


def test_layer_kernel_reads_negative_weights_as_silent():
    """A weight below 0 raises no ramp bit in the scan engine; the kernel's
    ``#{times <= t} - #{times + w <= t}`` count clamps it to 0 to agree."""
    from repro.core import neuron
    from repro.kernels import rnl_neuron
    times, w = _random_layer(3, 6, 32, 12)
    w = w - 3
    cfg = neuron.NeuronConfig(n_inputs=32, threshold=6, t_steps=16)
    fire, clip = rnl_neuron.rnl_fire_times_layer(
        times, w, t_steps=16, threshold=6, k=2, with_clip=True)
    sim = neuron.simulate_neuron(
        jnp.broadcast_to(times[:, :, None, :], (3, 6, 12, 32)),
        jnp.broadcast_to(w[:, None, :, :], (3, 6, 12, 32)), cfg)
    np.testing.assert_array_equal(np.asarray(fire),
                                  np.asarray(sim.fire_time))
    np.testing.assert_array_equal(np.asarray(clip),
                                  np.asarray(sim.clip_events))


def _random_layer(c, bsz, n, q):
    """(C, B, n) spike times over T=16 ticks (30% silent lines, some past
    the cycle) and (C, Q, n) 3-bit weights."""
    from repro.core.coding import NO_SPIKE
    kt, ks, kw = jax.random.split(jax.random.PRNGKey(bsz * n + q), 3)
    times = jax.random.randint(kt, (c, bsz, n), 0, 18)
    times = jnp.where(jax.random.uniform(ks, times.shape) < 0.3, NO_SPIKE,
                      times)
    return times, jax.random.randint(kw, (c, q, n), 0, 8)


def _check_layer_kernel(times, w, threshold, k):
    from repro.core import neuron
    from repro.kernels import rnl_neuron
    (c, bsz, n), q, t_steps = times.shape, w.shape[1], 16
    cfg = neuron.NeuronConfig(
        n_inputs=n, threshold=threshold, t_steps=t_steps,
        dendrite="pc_compact" if k is None else "catwalk",
        k=2 if k is None else k)
    fire, clip = rnl_neuron.rnl_fire_times_layer(
        times, w, t_steps=t_steps, threshold=threshold, k=k, with_clip=True)
    for engine in ("closed_form", "scan"):
        want = neuron.fire_times_bank(times, w, cfg, backend=engine)
        np.testing.assert_array_equal(np.asarray(fire), np.asarray(want))
    sim = neuron.simulate_neuron(
        jnp.broadcast_to(times[:, :, None, :], (c, bsz, q, n)),
        jnp.broadcast_to(w[:, None, :, :], (c, bsz, q, n)), cfg)
    np.testing.assert_array_equal(np.asarray(clip),
                                  np.asarray(sim.clip_events))


# ------------------------------------------------------------------ ssd_scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,L,p,n,chunk", [(2, 130, 16, 8, 64),
                                            (1, 64, 32, 16, 32),
                                            (4, 257, 8, 8, 128)])
def test_ssd_matches_oracle(bh, L, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(L), 4)
    u = jax.random.normal(ks[0], (bh, L, p), dtype)
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (bh, L)))
    b = (jax.random.normal(ks[2], (bh, L, n)) * 0.3).astype(dtype)
    c = (jax.random.normal(ks[3], (bh, L, n)) * 0.3).astype(dtype)
    got = ops.ssd_scan(u, ld, b, c, chunk=chunk, impl="pallas")
    want = ref.ssd_scan(u, ld, b, c)
    atol = 5e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=atol, rtol=atol)


def test_ssd_decay_zero_is_cumulative_outer():
    """log_decay = -inf-ish -> state resets each step: y_t = (C_t.B_t) u_t."""
    bh, L, p, n = 1, 32, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    u = jax.random.normal(ks[0], (bh, L, p))
    b = jax.random.normal(ks[1], (bh, L, n))
    c = jax.random.normal(ks[2], (bh, L, n))
    ld = jnp.full((bh, L), -30.0)
    got = ops.ssd_scan(u, ld, b, c, chunk=16, impl="pallas")
    want = jnp.einsum("zln,zln->zl", c, b)[..., None] * u
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_ssd_matches_oracle_long_state_carry():
    """Cross-chunk state carry: constant decay .9, impulse at t=0 only."""
    bh, L, p, n = 1, 200, 2, 2
    u = jnp.zeros((bh, L, p)).at[0, 0].set(1.0)
    b = jnp.ones((bh, L, n))
    c = jnp.ones((bh, L, n))
    ld = jnp.full((bh, L), jnp.log(0.9))
    got = ops.ssd_scan(u, ld, b, c, chunk=64, impl="pallas")
    want = ref.ssd_scan(u, ld, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------------ moe_gate
@pytest.mark.parametrize("t,e,k", [(7, 8, 2), (300, 64, 6), (1000, 128, 2)])
@pytest.mark.parametrize("renorm", [True, False])
def test_moe_gate_matches_oracle(t, e, k, renorm):
    logits = jax.random.normal(jax.random.PRNGKey(t + e), (t, e))
    p1, i1 = ops.moe_gate_topk(logits, k, renorm, impl="pallas")
    p2, i2 = ref.moe_gate_topk(logits, k, renorm)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_moe_gate_probs_valid(seed):
    logits = jax.random.normal(jax.random.PRNGKey(seed), (64, 16)) * 3
    p, i = ops.moe_gate_topk(logits, 2, True, impl="pallas")
    p = np.asarray(p)
    assert (p >= 0).all() and (p <= 1.0 + 1e-6).all()
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-5)
    i = np.asarray(i)
    assert (i[:, 0] != i[:, 1]).all()       # distinct experts


# ------------------------------------------------------- ssd_scan_mh
@pytest.mark.parametrize("bsz,h,L,p,n", [(2, 3, 130, 16, 8),
                                         (1, 8, 64, 32, 16)])
def test_ssd_mh_pallas_vs_ref(bsz, h, L, p, n):
    """Multi-head SSD (shared B/C): pallas head-folded path == the
    head-inside-einsum chunked ref == per-head token-scan oracle."""
    ks = jax.random.split(jax.random.PRNGKey(h * L), 4)
    u = jax.random.normal(ks[0], (bsz, h, L, p), jnp.float32)
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (bsz, h, L)))
    b = jax.random.normal(ks[2], (bsz, L, n)) * 0.3
    c = jax.random.normal(ks[3], (bsz, L, n)) * 0.3
    got_pl = ops.ssd_scan_mh(u, ld, b, c, chunk=32, impl="pallas")
    got_ref = ops.ssd_scan_mh(u, ld, b, c, chunk=32, impl="ref")
    # oracle: per-(batch, head) exact token scan with repeated B/C
    u_k = u.reshape(bsz * h, L, p)
    ld_k = ld.reshape(bsz * h, L)
    b_k = jnp.repeat(b[:, None], h, axis=1).reshape(bsz * h, L, n)
    c_k = jnp.repeat(c[:, None], h, axis=1).reshape(bsz * h, L, n)
    want = ref.ssd_scan(u_k, ld_k, b_k, c_k).reshape(bsz, h, L, p)
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(want),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               atol=5e-4, rtol=5e-4)


def test_ssd_mh_grad_flows():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    u = jax.random.normal(ks[0], (1, 2, 64, 8))
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (1, 2, 64)))
    b = jax.random.normal(ks[2], (1, 64, 4)) * 0.3
    c = jax.random.normal(ks[3], (1, 64, 4)) * 0.3
    g = jax.grad(lambda u: jnp.sum(
        ops.ssd_scan_mh(u, ld, b, c, chunk=32, impl="ref") ** 2))(u)
    assert not bool(jnp.isnan(g).any())
    assert float(jnp.abs(g).max()) > 0
