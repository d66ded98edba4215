"""Learn-while-serving (DESIGN.md §5.5): online STDP on live traffic,
snapshot durability, crash recovery with exactly-once replay, and the
backpressure pause — the serve-path robustness contract.

The gates mirror the engine's own guarantees:

* a learning engine's outputs AND final weights are bit-exact against a
  jitted ``network.step`` replay over the same batch composition;
* learning-off crash recovery reproduces every retired output bit-exactly
  (slot outputs are batch-composition-invariant, so replaying uncommitted
  streams from a restored snapshot changes nothing);
* learning-on crash recovery lands on the exact weights of a
  deterministic replay from the snapshot's step — for the expectation
  STDP rule and for the seeded stochastic rule (keys fold the persistent
  ``step_id``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coding, layer, network
from repro.serve import tnn_engine
from repro.train import checkpoint as CK
from repro.train import fault_tolerance as FT

NO_SPIKE = int(coding.NO_SPIKE)


def _net(recurrent=True):
    l1 = layer.TNNLayer(n_columns=2, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=12, dendrite="catwalk", k=2)
    l2 = layer.TNNLayer(n_columns=3, rf_size=2, n_neurons=2, threshold=4,
                        t_steps=12, dendrite="rnl", recurrent=recurrent)
    return network.make_network([l1, l2])


def _params(net, seed=0):
    return network.init_network(jax.random.PRNGKey(seed), net)


def _streams(net, n_req, max_cycles=4, min_cycles=1, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_req):
        n_cyc = int(rng.integers(min_cycles, max_cycles + 1))
        t = rng.integers(0, 20, size=(n_cyc, net.n_inputs))
        out.append(np.where(t >= 10, NO_SPIKE, t).astype(np.int32))
    return out


def _scfg(**kw):
    kw.setdefault("backend", "closed_form")
    return tnn_engine.TNNServeConfig(**kw)


def _weights_equal(ps_a, ps_b):
    return all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(ps_a, ps_b))


# ---------------------------------------------------- learning semantics
@pytest.mark.parametrize("recurrent", [False, True])
def test_learning_engine_matches_manual_step_replay(recurrent):
    """Same-length streams fill all slots at step 0 and retire together,
    so the engine's batch composition is known exactly — its outputs and
    final weights must match a jitted network.step loop over those
    batches (jitted, because the engine's step is jitted and eager XLA
    differs in float rounding)."""
    net = _net(recurrent)
    params = _params(net)
    B = 3
    streams = [s[:4] for s in _streams(net, B, max_cycles=4, min_cycles=4)]
    eng = tnn_engine.TNNEngine(params, net, _scfg(n_slots=B, learn=True))
    results = eng.serve(streams)

    pinned = network.make_network([
        dataclasses.replace(lc, backend="closed_form") for lc in net.layers])
    stepj = jax.jit(lambda p, v, c: network.step(p, v, pinned, carry=c))
    p = tuple(jnp.asarray(w) for w in params)
    carry = tuple(jnp.full((B, lc.n_outputs), NO_SPIKE, jnp.int32)
                  if lc.recurrent else None for lc in net.layers)
    outs = [[] for _ in range(B)]
    for c in range(4):
        batch = jnp.asarray(np.stack([s[c] for s in streams]))
        res = stepj(p, batch, carry)
        p, carry = res.params, res.carry
        for i in range(B):
            outs[i].append(np.asarray(res.out)[i])
    for i in range(B):
        np.testing.assert_array_equal(np.stack(outs[i]), results[i])
    assert _weights_equal(eng.params, p)
    assert eng.n_stdp_updates == 4


def test_learning_step_outputs_match_inference_step():
    """Outputs are computed at the PRE-update weights: the first gamma
    cycle of a learning engine is bit-exact with learning off (later
    cycles legitimately diverge — the weights moved)."""
    net = _net()
    params = _params(net)
    streams = [s[:1] for s in _streams(net, 4, seed=3)]
    r_off = tnn_engine.TNNEngine(params, net, _scfg(n_slots=4)).serve(streams)
    r_on = tnn_engine.TNNEngine(
        params, net, _scfg(n_slots=4, learn=True)).serve(streams)
    for a, b in zip(r_off, r_on):
        np.testing.assert_array_equal(a, b)


def test_stdp_cadence_and_drift_stats():
    net = _net()
    params = _params(net)
    streams = [s[:6] for s in _streams(net, 2, max_cycles=6, min_cycles=6)]
    eng = tnn_engine.TNNEngine(
        params, net, _scfg(n_slots=2, learn=True, stdp_every=3))
    eng.serve(streams)
    # 6 steps, updates on step_id 0 and 3
    assert eng.n_steps == 6 and eng.n_stdp_updates == 2
    st = eng.stats()
    assert st["n_stdp_updates"] == 2.0
    assert st["step_id"] == 6.0
    # learning moved the weights; drift norms report it per layer
    assert st["weight_drift_l0"] > 0.0
    assert "weight_drift_l1" in st
    # an inference engine reports the counters but no drift keys
    st0 = tnn_engine.TNNEngine(params, net, _scfg(n_slots=2)).stats()
    assert st0["n_stdp_updates"] == 0.0 and "weight_drift_l0" not in st0


def test_learning_never_recompiles_on_weight_update():
    """Weights are explicit jit arguments: a long learning run holds ONE
    learn variant no matter how many updates it applies."""
    net = _net(recurrent=False)
    params = _params(net)
    eng = tnn_engine.TNNEngine(params, net, _scfg(n_slots=2, learn=True))
    eng.serve([s[:5] for s in _streams(net, 4, max_cycles=5, min_cycles=5)])
    assert eng.n_stdp_updates == eng.n_steps
    st = eng.stats()
    assert st["jit_variants"] == 1.0        # the single learn variant
    assert set(eng._variants) == {(None, True)}


# ------------------------------------------------------- backpressure
def test_learning_pauses_under_queue_pressure_and_resumes():
    net = _net()
    params = _params(net)
    streams = [s[:1] for s in _streams(net, 9, seed=5)]
    eng = tnn_engine.TNNEngine(
        params, net,
        _scfg(n_slots=1, learn=True, max_pending=16,
              learn_pause_queue_frac=0.25))
    for s in streams:
        eng.submit(s)
    paused_steps = 0
    while eng.pool.has_work:
        eng.step()
        paused_steps += int(eng.learning_paused)
    # 9 single-cycle streams through 1 slot: queue holds >= 4 (frac 0.25)
    # for the first steps -> learning paused; it resumes as the queue
    # drains, so some (not all) steps learned
    assert paused_steps > 0
    assert 0 < eng.n_stdp_updates < eng.n_steps
    st = eng.stats()
    assert st["n_learn_pauses"] >= 1.0
    assert st["learning_paused"] == 0.0     # pressure cleared by the end
    # inference never paused: every volley was served
    assert eng.pool.n_retired == len(streams)


def test_learning_pauses_on_slow_steps():
    net = _net()
    params = _params(net)
    eng = tnn_engine.TNNEngine(
        params, net, _scfg(n_slots=2, learn=True, learn_pause_step_s=1e-9))
    stream = _streams(net, 1, max_cycles=3, min_cycles=3)[0]
    eng.serve([stream])
    # step 0 learns (no previous latency); every later step sees the
    # previous step's wall-clock over the (absurd) threshold and sheds
    assert eng.n_stdp_updates == 1
    assert eng.n_learn_pauses >= 1


def test_learn_config_validation():
    net = _net()
    params = _params(net)
    with pytest.raises(ValueError, match="stdp_every"):
        tnn_engine.TNNEngine(params, net, _scfg(learn=True, stdp_every=0))
    with pytest.raises(ValueError, match="checkpoint_dir"):
        tnn_engine.TNNEngine(params, net, _scfg(checkpoint_every=10))
    with pytest.raises(ValueError, match="max_pending"):
        tnn_engine.TNNEngine(
            params, net, _scfg(learn=True, learn_pause_queue_frac=0.5))
    with pytest.raises(ValueError, match="resume"):
        tnn_engine.TNNEngine(params, net, _scfg(), resume=True)


# ------------------------------------------------- snapshots + resume
def test_snapshot_cadence_and_resume(tmp_path):
    net = _net()
    params = _params(net)
    scfg = _scfg(n_slots=2, learn=True, checkpoint_dir=str(tmp_path),
                 checkpoint_every=2, checkpoint_keep=100,
                 checkpoint_async=False)
    eng = tnn_engine.TNNEngine(params, net, scfg)
    eng.serve([s[:3] for s in _streams(net, 4, max_cycles=3, min_cycles=3)])
    eng.checkpoint_wait()
    assert eng.n_snapshots == eng.step_id // 2
    assert CK.latest_step(tmp_path) == (eng.step_id // 2) * 2
    # a fresh engine resumes from the latest snapshot: weights + counters
    eng2 = tnn_engine.TNNEngine(params, net, scfg, resume=True)
    assert eng2.step_id == CK.latest_step(tmp_path)
    assert eng2.n_restores == 1
    snap = CK.restore_checkpoint(
        tmp_path,
        {"params": tuple(jnp.asarray(p) for p in params),
         "counters": np.zeros(2, np.int32)})
    assert _weights_equal(eng2.params, snap["params"])
    assert eng2.n_stdp_updates == int(np.asarray(snap["counters"])[1])
    # resume with an empty dir is a clean cold start
    eng3 = tnn_engine.TNNEngine(
        params, net,
        _scfg(n_slots=2, checkpoint_dir=str(tmp_path / "empty"),
              checkpoint_every=2),
        resume=True)
    assert eng3.step_id == 0 and eng3.n_restores == 0


def test_async_snapshot_is_step_consistent(tmp_path):
    """The async writer serializes the weights AS OF its step: the state
    is copied to host numpy before the thread starts, so later STDP
    updates can never leak into an in-flight save."""
    net = _net(recurrent=False)
    params = _params(net)
    scfg = _scfg(n_slots=2, learn=True, checkpoint_dir=str(tmp_path),
                 checkpoint_every=10, checkpoint_keep=100,
                 checkpoint_async=True)
    eng = tnn_engine.TNNEngine(params, net, scfg)
    streams = [s[:25] for s in
               _streams(net, 2, max_cycles=25, min_cycles=25)]
    for s in streams:
        eng.submit(s)
    while eng.pool.has_work and eng.n_snapshots == 0:
        eng.step()
    at_snap = tuple(np.asarray(p) for p in eng.params)
    while eng.pool.has_work:
        eng.step()          # keep learning while the writer may still run
    eng.checkpoint_wait()
    assert eng.step_id == 25 and eng.n_snapshots == 2
    snap = CK.restore_checkpoint(
        tmp_path,
        {"params": tuple(jnp.asarray(p) for p in params),
         "counters": np.zeros(2, np.int32)},
        step=10)
    assert _weights_equal(snap["params"], at_snap)


# ---------------------------------------------------- crash recovery
def _one_shot_failure(at_step, host_id=1):
    fired = []

    def injector(step_id):
        if step_id >= at_step and not fired:
            fired.append(step_id)
            raise FT.WorkerFailure(host_id, "(injected)")

    return injector


def test_serve_resilient_inference_bit_exact(tmp_path):
    """Learning off: the interrupted+replayed run returns every stream's
    outputs bit-exact vs the uninterrupted engine, exactly once."""
    net = _net()
    params = _params(net)
    streams = _streams(net, 7, seed=11)
    ref = tnn_engine.TNNEngine(params, net, _scfg(n_slots=2)).serve(streams)
    scfg = _scfg(n_slots=2, checkpoint_dir=str(tmp_path),
                 checkpoint_every=2, checkpoint_keep=100,
                 checkpoint_async=False)
    eng = tnn_engine.TNNEngine(params, net, scfg)
    mon = FT.HeartbeatMonitor(1)
    results, report = tnn_engine.serve_resilient(
        eng, streams, failure_injector=_one_shot_failure(5), monitor=mon)
    assert report["restarts"] == 1 and report["failed_hosts"] == [1]
    assert len(report["restored_steps"]) == 1
    assert eng.n_restores == 1
    for a, b in zip(ref, results):
        np.testing.assert_array_equal(a, b)
    # exactly-once: committed streams were not resubmitted
    s = report["restored_steps"][0]
    assert report["resubmitted"][0]
    assert len(report["resubmitted"][0]) < len(streams)
    assert mon.hosts[0].step_times  # the driver beat the monitor


@pytest.mark.parametrize("stdp_seed", [None, 123])
def test_serve_resilient_learning_replays_weight_trajectory(
        tmp_path, stdp_seed):
    """Learning on: after restore-and-replay the engine's final weights
    are bit-exact vs a deterministic replay from the snapshot's step —
    the restored counters re-key the stochastic rule identically."""
    net = _net()
    params = _params(net)
    streams = _streams(net, 7, seed=13)
    scfg = _scfg(n_slots=2, learn=True, stdp_seed=stdp_seed,
                 checkpoint_dir=str(tmp_path), checkpoint_every=2,
                 checkpoint_keep=100, checkpoint_async=False)
    eng = tnn_engine.TNNEngine(params, net, scfg)
    results, report = tnn_engine.serve_resilient(
        eng, streams, failure_injector=_one_shot_failure(5, host_id=2))
    assert report["restarts"] == 1
    s = report["restored_steps"][0]
    replay_idx = report["resubmitted"][0]
    # reconstruct the post-restore engine from the snapshot and replay
    snap = CK.restore_checkpoint(
        tmp_path,
        {"params": tuple(jnp.asarray(p) for p in params),
         "counters": np.zeros(2, np.int32)},
        step=s)
    eng2 = tnn_engine.TNNEngine(
        snap["params"], net,
        _scfg(n_slots=2, learn=True, stdp_seed=stdp_seed))
    eng2.step_id = s
    eng2.n_stdp_updates = int(np.asarray(snap["counters"])[1])
    r2 = eng2.serve([streams[i] for i in replay_idx])
    assert _weights_equal(eng.params, eng2.params)
    assert eng.n_stdp_updates == eng2.n_stdp_updates
    for i, out in zip(replay_idx, r2):
        np.testing.assert_array_equal(results[i], out)


def test_serve_resilient_no_snapshot_restores_initial_weights(tmp_path):
    """A failure before the first snapshot rolls back to construction:
    the implicit step-0 commit point, with every stream replayed."""
    net = _net()
    params = _params(net)
    streams = _streams(net, 4, seed=17)
    ref = tnn_engine.TNNEngine(params, net, _scfg(n_slots=2)).serve(streams)
    scfg = _scfg(n_slots=2, checkpoint_dir=str(tmp_path),
                 checkpoint_every=1000)
    eng = tnn_engine.TNNEngine(params, net, scfg)
    results, report = tnn_engine.serve_resilient(
        eng, streams, failure_injector=_one_shot_failure(1))
    assert report["restored_steps"] == [0]
    assert report["resubmitted"][0] == list(range(len(streams)))
    for a, b in zip(ref, results):
        np.testing.assert_array_equal(a, b)


def test_serve_resilient_exhausts_restarts(tmp_path):
    net = _net()
    params = _params(net)
    scfg = _scfg(n_slots=2, checkpoint_dir=str(tmp_path),
                 checkpoint_every=2)
    eng = tnn_engine.TNNEngine(params, net, scfg)

    def always(step_id):
        raise FT.WorkerFailure(0, "(always failing)")

    with pytest.raises(FT.WorkerFailure):
        tnn_engine.serve_resilient(
            eng, _streams(net, 3), failure_injector=always, max_restarts=2)
    assert eng.n_restores == 2


def test_restore_clears_pool_and_counts(tmp_path):
    net = _net()
    params = _params(net)
    scfg = _scfg(n_slots=2, checkpoint_dir=str(tmp_path),
                 checkpoint_every=2, checkpoint_async=False)
    eng = tnn_engine.TNNEngine(params, net, scfg)
    for s in _streams(net, 5, seed=19):
        eng.submit(s)
    for _ in range(3):
        eng.step()
    assert eng.pool.has_work
    s = eng.restore()
    assert s == 2                       # latest snapshot
    assert eng.step_id == 2
    assert not eng.pool.has_work        # live + pending dropped
    assert eng.n_restores == 1
    # and serving continues normally after the rollback
    out = eng.serve(_streams(net, 2, seed=23))
    assert len(out) == 2
