"""Slot machinery + TNN serving engine: scheduling contract, continuous
re-fill, per-slot retirement, and bit-exactness of engine outputs vs
unbatched TNNNetwork inference across all four neuron-bank backends."""

import asyncio

import jax
import numpy as np
import pytest

from repro.core import coding, layer, network
from repro.serve import tnn_engine
from repro.serve import SlotPool, latency_summary

NO_SPIKE = int(coding.NO_SPIKE)


# ---------------------------------------------------------- slot pool
def test_pool_fifo_admission_lowest_slot_first():
    pool = SlotPool(2)
    entries = [pool.submit(f"r{i}") for i in range(4)]
    assert [e.seq for e in entries] == [0, 1, 2, 3]
    placed = pool.admit()
    assert [(idx, e.item) for idx, e in placed] == [(0, "r0"), (1, "r1")]
    assert pool.n_pending == 2 and pool.n_live == 2
    # nothing free -> admit is a no-op
    assert pool.admit() == []


def test_pool_refill_preserves_queue_order():
    pool = SlotPool(2)
    for i in range(5):
        pool.submit(i)
    pool.admit()
    pool.retire(1)                       # slot 1 frees first
    placed = pool.admit()
    assert [(idx, e.item) for idx, e in placed] == [(1, 2)]
    pool.retire(0)
    pool.retire(1)
    placed = pool.admit()                # both free: FIFO into slots 0, 1
    assert [(idx, e.item) for idx, e in placed] == [(0, 3), (1, 4)]
    assert not pool.n_pending


def test_pool_retire_bookkeeping_and_errors():
    pool = SlotPool(2)
    pool.submit("a")
    pool.admit()
    entry = pool.retire(0)
    assert entry.item == "a"
    assert entry.retired_at >= entry.admitted_at >= entry.submitted_at
    assert pool.n_retired == 1 and not pool.has_work
    with pytest.raises(ValueError):
        pool.retire(0)                   # already empty
    with pytest.raises(ValueError):
        SlotPool(0)


def test_latency_summary():
    pool = SlotPool(1)
    for i in range(3):
        pool.submit(i)
    done = []
    while pool.has_work:
        pool.admit()
        done.append(pool.retire(0))
    s = latency_summary(done)
    assert s["n"] == 3.0
    assert s["latency_ms_max"] >= s["latency_ms_p95"] >= s["latency_ms_p50"]
    assert s["latency_ms_mean"] >= s["wait_ms_mean"] >= 0.0
    assert latency_summary([]) == {}


# ------------------------------------------------------------- engine
def _small_net():
    l1 = layer.TNNLayer(n_columns=2, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=12, dendrite="catwalk", k=2)
    return network.make_network([l1])


def _params(net, seed=0):
    return network.init_network(jax.random.PRNGKey(seed), net)


def _streams(net, n_req, max_cycles=3, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_req):
        n_cyc = int(rng.integers(1, max_cycles + 1))
        t = rng.integers(0, 20, size=(n_cyc, net.n_inputs))
        out.append(np.where(t >= 10, NO_SPIKE, t).astype(np.int32))
    return out


@pytest.mark.parametrize("backend",
                         ["scan", "closed_form", "event", "pallas", "auto"])
def test_engine_bit_exact_vs_unbatched(backend):
    """Slot batching must not change a single output spike time."""
    net = _small_net()
    params = _params(net)
    streams = _streams(net, n_req=6)
    eng = tnn_engine.TNNEngine(
        params, net,
        tnn_engine.TNNServeConfig(n_slots=2, backend=backend))
    results = eng.serve(streams)
    for stream, result in zip(streams, results):
        ref = tnn_engine.reference_outputs(params, net, stream)
        np.testing.assert_array_equal(ref, result)
    assert eng.pool.n_retired == len(streams)


def test_engine_continuous_refill_no_barrier():
    """A long request must not block short ones: with 2 slots, one 8-cycle
    request and five 1-cycle requests, the shorts drain through the other
    slot while the long one runs; total steps == the long request."""
    net = _small_net()
    params = _params(net)
    long = _streams(net, 1, seed=1)[0][:1].repeat(8, axis=0)
    shorts = [s[:1] for s in _streams(net, 5, seed=2)]
    eng = tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(n_slots=2,
                                               backend="closed_form"))
    req_long = eng.submit(long)
    req_shorts = [eng.submit(s) for s in shorts]
    finished = eng.run()
    assert eng.n_steps == 8
    # completion order: each short finishes in its own step, long one last
    assert [r.req_id for r in finished] == \
        [r.req_id for r in req_shorts] + [req_long.req_id]
    # bit-exact even for the request that spanned many refills
    np.testing.assert_array_equal(
        tnn_engine.reference_outputs(params, net, long), req_long.result())


def test_engine_step_retires_per_slot():
    """Requests retire the step their stream ends, not when the batch
    drains; freed slots admit pending work the next step."""
    net = _small_net()
    params = _params(net)
    eng = tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(n_slots=2,
                                               backend="closed_form"))
    a = eng.submit(_streams(net, 1, seed=3)[0][:2])   # 2 cycles
    b = eng.submit(_streams(net, 1, seed=4)[0][:1])   # 1 cycle
    c = eng.submit(_streams(net, 1, seed=5)[0][:1])   # queued behind a, b
    assert [r.req_id for r in eng.step()] == [b.req_id]
    assert eng.pool.n_pending == 1                    # c admitted next step
    retired = eng.step()                              # ...and both finish
    assert sorted(r.req_id for r in retired) == \
        sorted([a.req_id, c.req_id])
    assert not eng.pool.has_work


def test_engine_stats_and_validation():
    net = _small_net()
    params = _params(net)
    eng = tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(n_slots=2,
                                               backend="closed_form"))
    with pytest.raises(ValueError):
        eng.submit(np.zeros((2, net.n_inputs + 1), np.int32))
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0, net.n_inputs), np.int32))
    # negative spike times would corrupt the density measurement and feed
    # the event engine's breakpoint sort out of contract — reject
    bad = np.zeros((1, net.n_inputs), np.int32)
    bad[0, 0] = -3
    with pytest.raises(ValueError, match="non-negative"):
        eng.submit(bad)
    eng.serve(_streams(net, 4))
    st = eng.stats()
    assert st["n_retired"] == 4.0
    assert 0.0 < st["slot_occupancy"] <= 1.0
    assert st["volleys_per_s"] > 0.0
    assert st["latency_ms_mean"] > 0.0
    # single (n_inputs,) volley promotes to one cycle
    one = eng.serve([np.full((net.n_inputs,), NO_SPIKE, np.int32)])[0]
    assert one.shape == (1, 2, 3)


def test_async_engine_matches_sync():
    net = _small_net()
    params = _params(net)
    streams = _streams(net, 6, seed=7)
    sync_eng = tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(n_slots=3,
                                               backend="closed_form"))
    expected = sync_eng.serve(streams)

    async_eng = tnn_engine.AsyncTNNEngine(tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(n_slots=3,
                                               backend="closed_form")))

    async def clients():
        return await asyncio.gather(
            *[async_eng.submit(s) for s in streams])

    got = asyncio.run(clients())
    for e, g in zip(expected, got):
        np.testing.assert_array_equal(e, g)


def test_async_pump_failure_rejects_waiting_clients():
    """A dying pump must fail outstanding futures, not strand them."""
    net = _small_net()
    eng = tnn_engine.TNNEngine(
        _params(net), net, tnn_engine.TNNServeConfig(n_slots=2,
                                                     backend="closed_form"))
    eng._fwd = lambda p, v, c: (_ for _ in ()).throw(RuntimeError("boom"))
    aeng = tnn_engine.AsyncTNNEngine(eng)

    async def client():
        return await aeng.submit(_streams(net, 1)[0])

    with pytest.raises(RuntimeError, match="boom"):
        asyncio.run(client())


def test_async_submit_retries_transient_queue_full():
    """Concurrent submitters over a tiny pending queue: without retry the
    burst rejects deterministically; with the bounded retry every client
    rides through (the pump drains the queue between backoffs) and the
    results stay bit-exact."""
    net = _small_net()
    params = _params(net)
    streams = _streams(net, 6, seed=21)
    expected = tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(
            n_slots=2, backend="closed_form")).serve(streams)

    def make(retries):
        eng = tnn_engine.TNNEngine(
            params, net, tnn_engine.TNNServeConfig(
                n_slots=2, backend="closed_form", max_pending=1))
        return tnn_engine.AsyncTNNEngine(
            eng, submit_retries=retries, submit_retry_delay_s=0.001)

    async def burst(aeng):
        return await asyncio.gather(*[aeng.submit(s) for s in streams])

    # retry disabled: the second submitter hits the full queue before any
    # step can drain it
    with pytest.raises(tnn_engine.slots.QueueFull):
        asyncio.run(burst(make(retries=0)))
    # bounded retry absorbs the burst
    got = asyncio.run(burst(make(retries=50)))
    for e, g in zip(expected, got):
        np.testing.assert_array_equal(e, g)


def test_async_submit_raises_after_retry_budget():
    """A queue that never drains must still surface QueueFull once the
    retry budget is spent — bounded, not infinite, patience."""
    net = _small_net()
    eng = tnn_engine.TNNEngine(
        _params(net), net, tnn_engine.TNNServeConfig(
            n_slots=1, backend="closed_form", max_pending=1))
    eng.submit(_streams(net, 1)[0])        # queue is now full
    eng.step = lambda: []                  # engine makes no progress
    aeng = tnn_engine.AsyncTNNEngine(
        eng, submit_retries=2, submit_retry_delay_s=0.001)

    async def client():
        return await aeng.submit(_streams(net, 1, seed=8)[0])

    with pytest.raises(tnn_engine.slots.QueueFull):
        asyncio.run(client())
    # every attempt (initial + 2 retries) counted as a rejection
    assert eng.pool.n_rejected == 3
    with pytest.raises(ValueError):
        tnn_engine.AsyncTNNEngine(eng, submit_retries=-1)
    with pytest.raises(ValueError):
        tnn_engine.AsyncTNNEngine(eng, submit_retry_delay_s=-0.1)


def test_reset_stats_keeps_pending_work():
    net = _small_net()
    eng = tnn_engine.TNNEngine(
        _params(net), net, tnn_engine.TNNServeConfig(n_slots=2,
                                                     backend="closed_form"))
    eng.serve(_streams(net, 2))            # warmup traffic
    eng.submit(_streams(net, 1, seed=9)[0])
    eng.reset_stats()
    assert eng.n_steps == 0 and eng.stats()["n_retired"] == 0.0
    eng.run()
    st = eng.stats()
    assert st["n_retired"] == 1.0 and st["n_steps"] >= 1.0
    assert st["latency_ms_mean"] > 0.0


def test_sparse_engine_compiles_compacted_stack():
    """A sparse engine must plumb static compaction widths into the
    jitted stack: layer 0 gets the measured+bucketed batch width, deeper
    layers the 1-WTA structural bound — and stay bit-exact."""
    l1 = layer.TNNLayer(n_columns=2, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=12, dendrite="catwalk", k=2)
    l2 = layer.TNNLayer(n_columns=1, rf_size=6, n_neurons=2, threshold=4,
                        t_steps=12, dendrite="pc_compact")
    net = network.make_network([l1, l2])
    params = _params(net)
    # sparse streams: ~2 active lines out of 8
    rng = np.random.default_rng(3)
    streams = []
    for _ in range(5):
        t = np.full((2, net.n_inputs), NO_SPIKE, np.int32)
        for row in t:
            hot = rng.choice(net.n_inputs, size=2, replace=False)
            row[hot] = rng.integers(0, 12, size=2)
        streams.append(t)
    eng = tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(n_slots=4,
                                               backend="event"))
    results = eng.serve(streams)
    for stream, result in zip(streams, results):
        np.testing.assert_array_equal(
            tnn_engine.reference_outputs(params, net, stream), result)
    assert eng.engine == "event"
    assert eng.stats()["steps_measured"] > 0
    # every sparse compile is keyed (bucket, learn) and carries widths
    sparse_keys = list(eng._variants)
    assert sparse_keys and all(k[0] is not None and not k[1]
                               for k in sparse_keys)
    widths = network.sparse_widths(net, sparse_keys[0][0])
    assert widths[0] == sparse_keys[0][0]
    # l2 reads l1's post-WTA lines: rf=6 over Q=3 blocks -> at most
    # (6-2)//3 + 2 = 3 active lines, capped at the 2 columns that exist
    assert widths[1] == 2


def test_sparse_widths_structural_bound():
    l1 = layer.TNNLayer(n_columns=4, rf_size=4, n_neurons=4, threshold=5,
                        t_steps=16)
    l2 = layer.TNNLayer(n_columns=2, rf_size=8, n_neurons=2, threshold=4,
                        t_steps=16)
    net = network.make_network([l1, l2])
    assert network.sparse_widths(net, 8) == (8, 3)   # (8-2)//4 + 2 = 3
    assert network.sparse_widths(net, 0) == (1, 3)   # floor at 1


def test_engine_backend_override_rewrites_layers():
    net = _small_net()
    eng = tnn_engine.TNNEngine(
        _params(net), net,
        tnn_engine.TNNServeConfig(n_slots=2, backend="scan"))
    assert all(lc.backend == "scan" for lc in eng.net.layers)
    assert eng.engine == "scan"
    # "auto" pins the network's auto layers to the engine it resolved to
    # once (the closed form off a TPU)
    eng2 = tnn_engine.TNNEngine(
        _params(net), net, tnn_engine.TNNServeConfig(n_slots=2))
    assert all(lc.backend == "auto" for lc in net.layers)
    assert all(lc.backend == eng2.engine for lc in eng2.net.layers)
    if jax.default_backend() == "cpu":
        assert eng2.engine == "closed_form"


def test_engine_backend_override_respects_explicit_layers():
    """An engine-level backend pins only backend="auto" layers — explicit
    per-layer choices survive (regression: __init__ used to clobber every
    layer)."""
    l1 = layer.TNNLayer(n_columns=2, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=12, dendrite="catwalk", k=2, backend="scan")
    l2 = layer.TNNLayer(n_columns=1, rf_size=6, n_neurons=2, threshold=4,
                        t_steps=12, dendrite="catwalk", k=2)  # auto
    net = network.make_network([l1, l2])
    params = _params(net)
    eng = tnn_engine.TNNEngine(
        params, net,
        tnn_engine.TNNServeConfig(n_slots=2, backend="closed_form"))
    assert [lc.backend for lc in eng.net.layers] == ["scan", "closed_form"]
    # and the mixed network still serves bit-exact
    streams = _streams(net, 3, seed=11)
    for stream, result in zip(streams, eng.serve(streams)):
        np.testing.assert_array_equal(
            tnn_engine.reference_outputs(params, net, stream), result)
