"""When ``TNNEngine.step`` measures its batch on the host.

The engine resolves its neuron-bank engine once, at construction
(``policy.resolve_engine``); the step never resolves again. Only an
explicit sparse engine (``event``, ``pallas_compact``) reads the batch:
its compaction width is bucketed from the layer-0 active-line count.
Everywhere else the step skips the measurement, serves the same outputs,
and keeps its ``tnn.measure`` span.

The expected widths here are replayed independently of the engine: every
stream is admitted at step 0 (no more streams than slots), so step k's
batch is row i = stream i's cycle k, or all ``NO_SPIKE`` once it ended.
"""

import types

import jax
import numpy as np
import pytest

from repro.core import coding, compaction, layer, network
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


def _replay_widths(net, streams):
    """The bucketed layer-0 compaction width of every step."""
    lc = net.layers[0]
    rf0 = np.asarray(lc.rf_index())
    return [compaction.bucket_width(
                int((batch[:, rf0] < lc.t_steps).sum(axis=-1).max()))
            for batch in _batches(net, streams)]


def _no_measurement(monkeypatch):
    def refuse(self, batch):
        raise AssertionError("the step measured a batch it cannot use")

    monkeypatch.setattr(tnn_engine.TNNEngine, "_layer0_active", refuse)


def _no_resolution(monkeypatch):
    """From here on the serve engine's own view of the policy module
    refuses to resolve (the banks' trace-time pass-through of their
    pinned engine name stays available)."""
    def refuse(*args, **kwargs):
        raise AssertionError("the step resolved the engine again")

    monkeypatch.setattr(tnn_engine, "engine_policy", types.SimpleNamespace(
        SPARSE_ENGINES=engine_policy.SPARSE_ENGINES, resolve_engine=refuse))


def _serve(net, params, streams, monkeypatch, platform=None, **scfg):
    """Construct the engine (on ``platform`` when given), then serve with
    resolution refused and check every result against the reference."""
    with monkeypatch.context() as m:
        if platform is not None:
            m.setattr(jax, "default_backend", lambda: platform)
        eng = tnn_engine.TNNEngine(
            params, net, tnn_engine.TNNServeConfig(n_slots=N_SLOTS, **scfg))
    _no_resolution(monkeypatch)
    results = eng.serve([s.copy() for s in streams])
    for stream, result in zip(streams, results):
        np.testing.assert_array_equal(
            tnn_engine.reference_outputs(params, net, stream), result)
    return eng, eng.stats()


@pytest.mark.parametrize("backend",
                         ["pallas", "closed_form", "scan", "auto"])
def test_explicit_dense_backend_skips_the_measurement(backend, monkeypatch):
    """A dense engine — named, or ``auto`` (the closed form off a TPU) —
    is resolved at construction only: the step resolves nothing, measures
    nothing, and serves bit-exact from the one compiled step."""
    _no_measurement(monkeypatch)
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    eng, st = _serve(net, params, _streams(net), monkeypatch,
                     backend=backend)
    assert st["n_steps"] == 3.0
    assert st["steps_measured"] == 0.0
    assert eng.engine == (backend if backend != "auto" else "closed_form")
    assert all(lc.backend == eng.engine for lc in eng.net.layers)
    assert st["jit_variants"] == 0.0


def test_auto_on_the_pallas_fast_path_skips_the_measurement(monkeypatch):
    """On a TPU ``auto`` resolves to the Pallas kernel at construction;
    the step then takes no measurement and runs pallas every step. (The
    platform is patched for the construction only: the kernels run in the
    interpreter.)"""
    _no_measurement(monkeypatch)
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    eng, st = _serve(net, params, _streams(net), monkeypatch,
                     platform="tpu", backend="auto")
    assert eng.engine == "pallas"
    assert all(lc.backend == "pallas" for lc in eng.net.layers)
    assert st["steps_measured"] == 0.0
    assert st["n_steps"] == 3.0 and st["jit_variants"] == 0.0


@pytest.mark.parametrize("backend", ["event", "pallas_compact"])
def test_explicit_sparse_backend_still_measures(backend, monkeypatch):
    """An explicit sparse engine compiles at the width bucketed from each
    batch's measured active count: one jit variant per distinct bucket."""
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    streams = _streams(net, seed=5)
    want = _replay_widths(net, streams)
    eng, st = _serve(net, params, streams, monkeypatch, backend=backend)
    assert eng.engine == backend
    assert st["steps_measured"] == st["n_steps"] == len(want)
    assert set(eng._variants) == {(w, False) for w in want}
