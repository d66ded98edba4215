"""When ``TNNEngine.step`` measures its batch on the host.

The batch density and the layer-0 active-line count feed only the engine
policy: ``auto`` ranks engines by them off the TPU Pallas fast path, and an
explicit sparse engine (``event``, ``pallas_compact``) takes its compaction
width from the active count. Everywhere else the step skips the measurement,
serves the same outputs, and keeps its ``tnn.measure`` span.

The expected resolutions here are replayed independently of the engine:
every stream is admitted at step 0 (no more streams than slots), so step k's
batch is row i = stream i's cycle k, or all ``NO_SPIKE`` once it ended.
"""

import jax
import numpy as np
import pytest

from repro.core import coding, layer, network
from repro.core import policy as engine_policy
from repro.serve import tnn_engine

NO_SPIKE = int(coding.NO_SPIKE)
N_SLOTS = 4


def _net():
    l1 = layer.TNNLayer(n_columns=2, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=12, dendrite="catwalk", k=2)
    l2 = layer.TNNLayer(n_columns=1, rf_size=6, n_neurons=2, threshold=4,
                        t_steps=12, dendrite="pc_compact")
    return network.make_network([l1, l2])


def _streams(net, seed=0):
    """N_SLOTS streams of 1-3 cycles, about two active lines in eight."""
    rng = np.random.default_rng(seed)
    out = []
    for n_cyc in (3, 1, 2, 3)[:N_SLOTS]:
        t = np.full((n_cyc, net.n_inputs), NO_SPIKE, np.int32)
        for row in t:
            hot = rng.choice(net.n_inputs, size=2, replace=False)
            row[hot] = rng.integers(0, 12, size=2)
        out.append(t)
    return out


def _batches(net, streams):
    """The (N_SLOTS, n_inputs) batch of every step, slot i = stream i."""
    for k in range(max(len(s) for s in streams)):
        batch = np.full((N_SLOTS, net.n_inputs), NO_SPIKE, np.int32)
        for i, s in enumerate(streams):
            if k < len(s):
                batch[i] = s[k]
        yield batch


def _replay(pol, backend, net, streams):
    """(density, Resolution) of every step, measured as the engine did
    before the measurement was gated."""
    lc = net.layers[0]
    t = lc.t_steps
    rf0 = np.asarray(lc.rf_index())
    shape = engine_policy.BankShape(
        pairs=N_SLOTS * lc.n_columns * lc.n_neurons, n_lines=lc.rf_total,
        t_steps=t)
    out = []
    for batch in _batches(net, streams):
        density = float(np.mean(batch < t))
        max_active = int((batch[:, rf0] < t).sum(axis=-1).max())
        out.append((density, pol.resolve(
            backend, density=density, max_active=max_active,
            column_counts=net.column_counts, shape=shape)))
    return out


def _serve(net, params, streams, **scfg):
    eng = tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(n_slots=N_SLOTS, **scfg))
    results = eng.serve([s.copy() for s in streams])
    for stream, result in zip(streams, results):
        np.testing.assert_array_equal(
            tnn_engine.reference_outputs(params, net, stream), result)
    return eng, eng.stats()


def _no_measurement(monkeypatch):
    def refuse(self, batch):
        raise AssertionError("the step measured a batch it cannot use")

    monkeypatch.setattr(tnn_engine.TNNEngine, "_layer0_active", refuse)


@pytest.mark.parametrize("backend", ["pallas", "closed_form"])
def test_explicit_dense_backend_skips_the_measurement(backend, monkeypatch):
    """An explicit non-sparse engine runs as named: the step measures
    nothing, reports no density, and serves bit-exact."""
    _no_measurement(monkeypatch)
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    eng, st = _serve(net, params, _streams(net), backend=backend)
    assert st["n_steps"] == 3.0
    assert st["steps_measured"] == 0.0
    assert "density_mean" not in st
    assert st[f"steps_{backend}"] == st["n_steps"]
    assert not eng._fwd_alt


def test_auto_on_the_pallas_fast_path_skips_the_measurement(monkeypatch):
    """Where the Pallas kernel preempts ``auto`` (a TPU), the policy reads
    no measurement, so the step takes none and every step runs pallas."""
    _no_measurement(monkeypatch)
    monkeypatch.setattr(engine_policy.EnginePolicy, "_pallas_preempts",
                        lambda self, column_counts: True)
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    eng, st = _serve(net, params, _streams(net), backend="auto")
    assert st["steps_measured"] == 0.0
    assert "density_mean" not in st
    assert st["steps_pallas"] == st["n_steps"] == 3.0
    assert not any(k.startswith("predicted_us_mean_") for k in st)


@pytest.mark.parametrize("policy", ["cost", "density"])
def test_auto_off_the_fast_path_measures_every_step(policy):
    """``auto`` on the CPU measures every step and resolves each one from
    the batch it measured, as before the gate."""
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    streams = _streams(net, seed=3)
    pol = engine_policy.get_policy(policy)
    want = _replay(pol, "auto", net, streams)
    eng, st = _serve(net, params, streams, backend="auto", policy=policy)
    assert st["steps_measured"] == st["n_steps"] == len(want)
    assert st["density_mean"] == pytest.approx(
        np.mean([d for d, _ in want]))
    engines = [res.engine for _, res in want]
    for engine in set(engines):
        assert st[f"steps_{engine}"] == engines.count(engine)
    widths = {(res.engine, res.width, False) for _, res in want
              if res.width is not None}
    assert set(eng._fwd_alt) == widths
    if policy == "density":
        assert widths, "the sparse streams should resolve to event"


@pytest.mark.parametrize("backend", ["event", "pallas_compact"])
def test_explicit_sparse_backend_still_measures(backend):
    """An explicit sparse engine compiles at the width bucketed from each
    batch's measured active count: the same jit variants as before."""
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    streams = _streams(net, seed=5)
    want = _replay(engine_policy.default_policy(), backend, net, streams)
    eng, st = _serve(net, params, streams, backend=backend)
    assert st["steps_measured"] == st["n_steps"] == len(want)
    assert "density_mean" in st
    assert st[f"steps_{backend}"] == st["n_steps"]
    assert set(eng._fwd_alt) == {(backend, res.width, False)
                                 for _, res in want}
    assert all(res.width is not None for _, res in want)


def test_stage_densities_average_over_measured_steps():
    """Under pipelining the per-stage densities are means over the
    measured steps, and absent when no step measured."""
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    streams = _streams(net, seed=7)
    _, st = _serve(net, params, streams, backend="event",
                   pipeline_microbatches=2)
    batches = list(_batches(net, streams))
    t = net.layers[0].t_steps
    for i, (lo, hi) in enumerate([(0, 2), (2, 4)]):
        assert st[f"density_stage{i}_mean"] == pytest.approx(
            np.mean([np.mean(b[lo:hi] < t) for b in batches]))
    _, st = _serve(net, params, streams, backend="closed_form",
                   pipeline_microbatches=2)
    assert st["steps_measured"] == 0.0
    assert not any(k.startswith("density_stage") for k in st)
