"""Size a cell: the knee of an open loop, or throughput against slots.

    python chipbench/sweep.py --workload <open-loop cell> --rates 600,800,...
        [--seconds 8] [--seed 1]
    python chipbench/sweep.py --workload <closed-loop cell> --slots 64,128,...
        [--seconds 8] [--seed 1]

With ``--rates``, one process serves the cell's traffic at each rate in turn
through one program engine (set up and warmed once), each for ``--seconds``
of arrivals and then drained. Per rate it prints one JSON line: the p50 and
p95 latency (due to done) of the requests due in the first and the second
half of the arrivals, and the backlog (sent, not yet retired) at the end of
each half. Past the knee the backlog grows from one half to the next and the
second half's p95 runs away.

With ``--slots``, the cell's closed loop runs for ``--seconds`` at each slot
count, with twice as many clients as slots, on an engine built for it; per
count one JSON line gives the volleys served per second and the mean step.

Needs a TPU, like ``run.py``; nothing it prints is a benchmark result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _backlog(requests, t):
    return sum(1 for r in requests if r.sent <= t and not r.done <= t)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--rates")
    which.add_argument("--slots")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax
    import numpy as np

    from harness import loop, runner, spec, traffic

    cell = spec.find_cell(spec.load_benchmark(), args.workload)
    loop_kind = "open" if args.rates else "closed"
    if jax.devices()[0].platform != "tpu" or cell.traffic["loop"] != loop_kind:
        print(f"sweep: needs a TPU and a {loop_kind}-loop cell",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path.insert(0, str(ROOT / "src"))
    cfg, sut = cell.config, cell.system
    pool = sut.pool(cfg, args.seed)
    w0 = sut.init_params(
        jax.random.PRNGKey(traffic.seed_key_int(args.seed, 3)), cfg)
    if args.slots:
        for slots in [int(s) for s in args.slots.split(",")]:
            cfg_s = dict(cfg, n_slots=slots)
            tr = dict(cell.traffic, clients=2 * slots)
            eng = sut.engine(w0, cfg_s, cell.name)
            win = loop.Window(eng, pool, traffic.schedule(tr, 1.0, args.seed),
                              payload=sut.payload)
            runner._warmup(win, dataclasses.replace(cell, config=cfg_s),
                           win.sched)
            n0, t0 = eng.n_volleys, win.clock()
            win.run_closed(t0 + args.seconds)
            steps = np.asarray(win.steps[runner.CLOSED_WARMUP_STEPS:])
            print(json.dumps({
                "n_slots": slots,
                "volleys_per_s": (eng.n_volleys - n0) / (steps[-1, 1] - t0),
                "step_ms": 1e3 * float(np.mean(steps[:, 1] - steps[:, 0]))}),
                flush=True)
            eng.close()
        return 0
    eng = sut.engine(w0, cfg, cell.name)
    warm = loop.Window(eng, pool, traffic.schedule(cell.traffic, 1.0, 0),
                       payload=sut.payload)
    runner._warmup(warm, cell, warm.sched)
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = dict(cell.traffic, rate_per_s=rate)
        sched = traffic.schedule(tr, args.seconds, args.seed)
        win = loop.Window(eng, pool, sched, payload=sut.payload)
        t0 = win.clock()
        win.run_open(t0, t0 + args.seconds + 10.0)
        half = t0 + args.seconds / 2
        rows = {}
        for name, lo, hi in (("first", t0, half),
                             ("second", half, t0 + args.seconds)):
            lat = [(r.done if not math.isnan(r.done) else math.inf) - r.due
                   for r in win.requests if lo <= r.due < hi]
            rows[name] = {
                "p50_ms": 1e3 * runner.percentile(lat, 50),
                "p95_ms": 1e3 * runner.percentile(lat, 95),
                "backlog_at_end": _backlog(win.requests, hi)}
        steps = np.asarray(win.steps)
        print(json.dumps({"rate_per_s": rate, "requests": len(win.requests),
                          "lost": sum(r.out is None for r in win.requests),
                          "step_ms": 1e3 * float(np.mean(steps[:, 1]
                                                         - steps[:, 0])),
                          **rows}), flush=True)
    eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
