"""The serve step's profiler spans (``TNNEngine.step``).

A small learning engine serves a few steps under ``jax.profiler`` and the
trace is read back with ``jax.profiler.ProfileData``: every ``tnn.step``
holds its phase spans in order, without overlap, on the step's thread;
``tnn.snapshot`` shows exactly on the snapshot cadence; and a running
profiler changes no output and no weight.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro.core import coding, layer, network
from repro.serve import tnn_engine

NO_SPIKE = int(coding.NO_SPIKE)
PHASES = ["tnn.admit", "tnn.batch", "tnn.measure", "tnn.resolve", "tnn.h2d",
          "tnn.dispatch", "tnn.fetch", "tnn.retire"]
EVERY = 3


def _net():
    l1 = layer.TNNLayer(n_columns=2, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=12, dendrite="catwalk", k=2)
    l2 = layer.TNNLayer(n_columns=3, rf_size=2, n_neurons=2, threshold=4,
                        t_steps=12, dendrite="rnl")
    return network.make_network([l1, l2])


def _serve(ckpt_dir, trace_dir=None):
    """Serve a fixed set of streams on a fresh engine; returns the outputs,
    the final weights and the engine's step count."""
    net = _net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    rng = np.random.default_rng(0)
    streams = []
    for _ in range(6):
        t = rng.integers(0, 20, size=(int(rng.integers(1, 5)), net.n_inputs))
        streams.append(np.where(t >= 10, NO_SPIKE, t).astype(np.int32))
    eng = tnn_engine.TNNEngine(params, net, tnn_engine.TNNServeConfig(
        n_slots=3, backend="closed_form", learn=True,
        checkpoint_dir=str(ckpt_dir), checkpoint_every=EVERY))
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        outs = eng.serve(streams)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    eng.checkpoint_wait()
    return outs, [np.asarray(p) for p in eng.params], eng.step_id


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tmp_path_factory.mktemp("spans")
    traced = _serve(root / "ckpt_traced", root / "trace")
    plain = _serve(root / "ckpt_plain")
    path, = glob.glob(os.path.join(root / "trace", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    # (plane, line) of every tnn.* event, and the events of each line
    lines = {}
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats) if e.name == "tnn.step" else {})
                      for e in line.events if e.name.startswith("tnn.")]
            if events:
                lines[(plane.name, line.name)] = events
    return traced, plain, lines


def _steps(lines):
    (events,) = lines.values()
    steps = sorted((e for e in events if e[0] == "tnn.step"),
                   key=lambda e: e[1])
    inner = [e for e in events if e[0] != "tnn.step"]
    return steps, inner


def test_every_span_is_on_the_step_thread(served):
    _, _, lines = served
    assert len(lines) == 1, sorted(lines)


def test_each_step_holds_its_phases_in_order_without_overlap(served):
    (_, _, n_steps), _, lines = served
    steps, inner = _steps(lines)
    assert [e[3]["step_num"] for e in steps] == list(range(n_steps))
    held = 0
    for name, s, e, stats in steps:
        phases = sorted((p for p in inner if s <= p[1] and p[2] <= e),
                        key=lambda p: p[1])
        held += len(phases)
        names = [p[0] for p in phases]
        assert names[:len(PHASES)] == PHASES
        assert names[len(PHASES):] in ([], ["tnn.snapshot"])
        for a, b in zip(phases, phases[1:]):
            assert a[2] <= b[1]
    # no phase span lies outside a step
    assert held == len(inner)


def test_snapshot_spans_follow_the_cadence(served):
    (_, _, n_steps), _, lines = served
    steps, inner = _steps(lines)
    snaps = [p for p in inner if p[0] == "tnn.snapshot"]
    # step_num is step_id at the step's start; the snapshot is due once
    # the step has advanced it
    want = [num for num in range(n_steps) if (num + 1) % EVERY == 0]
    got = [stats["step_num"] for _, s, e, stats in steps
           if any(s <= p[1] and p[2] <= e for p in snaps)]
    assert want and got == want and len(snaps) == len(want)


def test_a_running_profiler_changes_no_output(served):
    (outs, weights, n_steps), (outs0, weights0, n_steps0), _ = served
    assert n_steps == n_steps0
    assert len(outs) == len(outs0)
    for a, b in zip(outs, outs0):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(weights, weights0):
        np.testing.assert_array_equal(a, b)
