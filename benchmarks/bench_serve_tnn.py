"""TNN serving benchmark: volleys/sec through the slot engine.

Sweeps slot-pool size (batch width) x neuron-bank backend over a fixed
synthetic client population and reports engine throughput, the unbatched
per-request baseline, and the batching speedup. Emits the usual CSV rows
plus a ``BENCH_serve_tnn.json`` artifact (see benchmarks/common.py).

CPU notes: ``closed_form`` is the honest CPU number; ``scan`` mirrors the
hardware tick loop; ``pallas`` runs the interpreter on CPU (validation, not
speed) and is only included with ``--backends ...,pallas``.

Run:  PYTHONPATH=src python -m benchmarks.bench_serve_tnn [--smoke]
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import (emit, note_meta, reset_results, smoke_mode,
                               spike_density, write_json)
from repro.core import network
from repro.serve import tnn_engine

from examples.serve_tnn import build_network, synth_clients


def bench_one(params, net, streams, n_slots: int, backend: str) -> float:
    """Serve the whole population once; returns engine volleys/sec."""
    eng = tnn_engine.TNNEngine(
        params, net,
        tnn_engine.TNNServeConfig(n_slots=n_slots, backend=backend))
    # warm the jit cache with the full workload: a sparse engine ("event")
    # compiles per measured width bucket as slot
    # composition shifts, so a single-stream warmup would leave compiles
    # inside the timed region. Serving the identical population replays the
    # exact batch sequence, hitting every variant the timed run will use.
    # reset so warmup steps don't pollute the emitted occupancy/latency
    eng.serve(list(streams))
    eng.reset_stats()
    for s in streams:
        eng.submit(s)
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    total = sum(s.shape[0] for s in streams)
    vps = total / dt
    st = eng.stats()
    emit(f"serve/tnn_B{n_slots}_{backend}", dt * 1e6 / total,
         f"{vps:.0f}_volleys_per_s_occ{st['slot_occupancy']:.2f}")
    return vps


def main(smoke: bool = False, backends=None) -> None:
    smoke = smoke or smoke_mode()
    reset_results()
    backends = backends or ["closed_form", "scan"]
    n_clients = 16 if smoke else 96
    slot_sweep = [2, 4] if smoke else [4, 8, 16, 32]

    net = build_network()
    params = network.init_network(jax.random.PRNGKey(0), net)
    streams = synth_clients(n_clients, n_features=4, n_fields=8,
                            t_max=net.layers[0].t_steps)
    total = sum(s.shape[0] for s in streams)
    note_meta(input_spike_density=spike_density(
        np.concatenate(streams, axis=0)))

    # naive per-request oracle (eager, unjitted) — the "no serving stack
    # at all" number; the fair batching baseline is the B=1 engine below.
    t0 = time.perf_counter()
    for s in streams[:max(n_clients // 8, 2)]:
        tnn_engine.reference_outputs(params, net, s)
    base_dt = time.perf_counter() - t0
    base_total = sum(s.shape[0] for s in streams[:max(n_clients // 8, 2)])
    emit("serve/tnn_naive_eager_reference", base_dt * 1e6 / base_total,
         f"{base_total / base_dt:.0f}_volleys_per_s")

    for backend in backends:
        base_vps = bench_one(params, net, streams, 1, backend)
        for n_slots in slot_sweep:
            vps = bench_one(params, net, streams, n_slots, backend)
            print(f"# B={n_slots:3d} {backend:12s} {vps:8.0f} volleys/s "
                  f"({vps / base_vps:.1f}x vs B=1 {backend}) "
                  f"[{total} volleys, {n_clients} clients]")

    # recurrent streams: same population through a stateful stack — each
    # slot carries its stream's previous-cycle volley (state in the slot),
    # so throughput includes the carry scatter/gather bookkeeping
    rnet = network.make_network(
        [dataclasses.replace(lc, recurrent=True) for lc in net.layers])
    rparams = network.init_network(jax.random.PRNGKey(0), rnet)
    n_slots = slot_sweep[-1]
    eng = tnn_engine.TNNEngine(
        rparams, rnet, tnn_engine.TNNServeConfig(n_slots=n_slots))
    results = eng.serve(list(streams))       # warmup + correctness pass
    for s, r in zip(streams, results):
        want = tnn_engine.reference_outputs(rparams, rnet, s)
        if not np.array_equal(want, r):      # carries must be inert to batching
            raise AssertionError("recurrent serve diverges from reference")
    eng.reset_stats()
    for s in streams:
        eng.submit(s)
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    emit(f"serve/tnn_B{n_slots}_recurrent", dt * 1e6 / total,
         f"{total / dt:.0f}_volleys_per_s_stateful")
    print(f"# B={n_slots:3d} recurrent     {total / dt:8.0f} volleys/s "
          f"[stateful slots, {n_clients} clients]")
    write_json("serve_tnn", smoke=smoke)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI plumbing validation")
    ap.add_argument("--backends", default=None,
                    help="comma list: closed_form,scan,event,auto,pallas")
    args = ap.parse_args()
    main(smoke=args.smoke,
         backends=args.backends.split(",") if args.backends else None)
