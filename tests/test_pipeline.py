"""Gamma-cycle pipelined forward (DESIGN.md §5.4): bit-exactness of
``network.forward(..., microbatches=M)`` vs the barriered M=1 schedule.

The pipeline schedule (M micro-batches streamed through the layer stack,
NO_SPIKE-padded warmup/drain ticks) is a pure re-ordering of layer-local
work, so outputs AND per-layer winners must match bit for bit — for every
backend, every micro-batch count (including M=1, M > B, and ragged
B % M != 0 splits), jitted and eager, and through the serve engine's
``pipeline_microbatches`` knob."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import coding, layer, network
from repro.serve import tnn_engine

NO_SPIKE = int(coding.NO_SPIKE)

BACKENDS = ("scan", "closed_form", "event", "pallas")


def _sparse_volleys(seed, bsz, n, t_max=22, t_steps=12):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, t_max, size=(bsz, n))
    return np.where(t >= t_steps, NO_SPIKE, t).astype(np.int32)


def _stack(depth=3, backend="scan", n_col=4, rf=4, q=4, t_steps=12):
    layers = [layer.TNNLayer(n_columns=n_col, rf_size=rf, n_neurons=q,
                             threshold=5, t_steps=t_steps,
                             dendrite="catwalk", k=2, backend=backend)]
    for _ in range(depth - 1):
        prev = layers[-1]
        layers.append(layer.TNNLayer(
            n_columns=prev.n_outputs // rf, rf_size=rf, n_neurons=q,
            threshold=4, t_steps=t_steps, dendrite="catwalk", k=2,
            backend=backend))
    return network.make_network(layers)


def _assert_pipelined_matches(params, v, net, microbatches, jit=False):
    ref_res = network.forward(params, v, net)
    ref, ref_win = ref_res.out, ref_res.winners
    fn = lambda p, x: network.forward(p, x, net, microbatches=microbatches)
    if jit:
        fn = jax.jit(fn)
    res = fn(params, v)
    out, win = res.out, res.winners
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert len(win) == len(ref_win)
    for got, want in zip(win, ref_win):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- backend sweeps
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("microbatches", [1, 2, 3, 8, 100])
def test_pipelined_bit_exact_all_backends(backend, microbatches):
    """M=1 (degenerate), ragged 8 % 3 != 0, M=B, and M > B splits all
    reproduce the barriered schedule exactly."""
    net = _stack(depth=2, backend=backend)
    params = network.init_network(jax.random.PRNGKey(0), net)
    v = jnp.asarray(_sparse_volleys(7, 8, net.n_inputs))
    _assert_pipelined_matches(params, v, net, microbatches)


@pytest.mark.parametrize("backend", ("scan", "closed_form", "event"))
def test_pipelined_deep_stack_jitted(backend):
    """Depth 3 under jit: the scan carry crosses two stage buffers."""
    net = _stack(depth=3, backend=backend)
    params = network.init_network(jax.random.PRNGKey(1), net)
    v = jnp.asarray(_sparse_volleys(3, 6, net.n_inputs))
    for m in (2, 4, 6):
        _assert_pipelined_matches(params, v, net, m, jit=True)


def test_pipelined_single_volley_and_batch_of_one():
    net = _stack(depth=2)
    params = network.init_network(jax.random.PRNGKey(2), net)
    v1 = jnp.asarray(_sparse_volleys(11, 1, net.n_inputs))
    _assert_pipelined_matches(params, v1, net, 4)          # B=1, M clamps
    rres = network.forward(params, v1[0], net)
    ref, ref_win = rres.out, rres.winners
    pres = network.forward(params, v1[0], net, microbatches=4)
    out, win = pres.out, pres.winners
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    for got, want in zip(win, ref_win):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pipelined_empty_batch_matches_barriered():
    """B=0 streams nothing and must mirror the barriered path's empties."""
    net = _stack(depth=2)
    params = network.init_network(jax.random.PRNGKey(6), net)
    v = jnp.zeros((0, net.n_inputs), jnp.int32)
    _assert_pipelined_matches(params, v, net, 4)


def test_pipelined_all_silent_and_dense_edges():
    """Warmup/drain padding is all-NO_SPIKE; a fully silent batch must be
    indistinguishable from padding, and a fully dense batch must not leak
    into neighbouring micro-batches."""
    net = _stack(depth=3)
    params = network.init_network(jax.random.PRNGKey(3), net)
    silent = jnp.full((5, net.n_inputs), NO_SPIKE, jnp.int32)
    dense = jnp.asarray(
        np.random.default_rng(5).integers(0, 12, size=(5, net.n_inputs)),
        jnp.int32)
    for v in (silent, dense, jnp.concatenate([silent[:2], dense[:3]])):
        for m in (1, 2, 5):
            _assert_pipelined_matches(params, v, net, m)


def test_pipelined_mixed_per_layer_backends():
    """Explicit per-layer backends ride through the pipeline untouched."""
    base = _stack(depth=3, backend="scan")
    layers = [dataclasses.replace(lc, backend=b) for lc, b in
              zip(base.layers, ("event", "closed_form", "scan"))]
    net = network.make_network(layers)
    params = network.init_network(jax.random.PRNGKey(4), net)
    v = jnp.asarray(_sparse_volleys(9, 7, net.n_inputs))
    _assert_pipelined_matches(params, v, net, 3)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 12), st.integers(1, 20), st.integers(0, 2 ** 31 - 1),
       st.sampled_from(("scan", "closed_form", "event")))
def test_pipelined_property_any_split(bsz, microbatches, seed, backend):
    """Property: any (B, M, workload, backend) draw is bit-exact — the
    ragged/degenerate splits fall out of the same invariant."""
    net = _stack(depth=2, backend=backend)
    params = network.init_network(jax.random.PRNGKey(seed % 997), net)
    v = jnp.asarray(_sparse_volleys(seed, bsz, net.n_inputs))
    _assert_pipelined_matches(params, v, net, microbatches)


# ------------------------------------------------------- serving path
def test_engine_pipelined_bit_exact_and_stage_stats():
    """TNNEngine(pipeline_microbatches=M) serves bit-exact vs the
    unbatched oracle and reports the effective micro-batch count."""
    net = _stack(depth=2)
    params = network.init_network(jax.random.PRNGKey(0), net)
    rng = np.random.default_rng(0)
    streams = [_sparse_volleys(int(rng.integers(1e9)),
                               int(rng.integers(1, 5)), net.n_inputs)
               for _ in range(9)]
    for m in (1, 2, 4, 9):
        eng = tnn_engine.TNNEngine(
            params, net,
            tnn_engine.TNNServeConfig(n_slots=4, pipeline_microbatches=m))
        results = eng.serve([s.copy() for s in streams])
        for s, r in zip(streams, results):
            np.testing.assert_array_equal(
                tnn_engine.reference_outputs(params, net, s), r)
        st_ = eng.stats()
        assert st_["pipeline_microbatches"] == float(min(m, 4))


def test_engine_pipelined_sparse_engine_widths():
    """backend="event" + pipelining: the static compaction widths measured
    on the whole slot batch cover every micro-batch (no dropped lines)."""
    net = _stack(depth=2)
    params = network.init_network(jax.random.PRNGKey(0), net)
    rng = np.random.default_rng(4)
    streams = [_sparse_volleys(int(rng.integers(1e9)), 3, net.n_inputs)
               for _ in range(6)]
    eng = tnn_engine.TNNEngine(
        params, net,
        tnn_engine.TNNServeConfig(n_slots=4, backend="event",
                                  pipeline_microbatches=2))
    results = eng.serve([s.copy() for s in streams])
    for s, r in zip(streams, results):
        np.testing.assert_array_equal(
            tnn_engine.reference_outputs(params, net, s), r)
    assert eng.engine == "event"
    assert eng.stats()["steps_measured"] == eng.stats()["n_steps"] > 0


def test_engine_rejects_bad_microbatch_count():
    net = _stack(depth=1)
    params = network.init_network(jax.random.PRNGKey(0), net)
    with pytest.raises(ValueError):
        tnn_engine.TNNEngine(
            params, net,
            tnn_engine.TNNServeConfig(n_slots=2, pipeline_microbatches=0))
