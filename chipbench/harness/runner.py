"""One run of one cell: set-up, the measured window, metrics, the check.

Everything that belongs to the system under test is reached through the
cell's system module (``cell.system``, ``systems/__init__.py``).

Set-up (``setup_s``, from process start to the window's start): the request
pool from the seed, the parameters from the seed, the program's engine, and
warm-up steps through that same engine with the cell's own shapes (a closed
loop's first steps; for an open loop one full batch of two-unit requests,
served to the end). Nothing compiles in the window after that.

After the window: in-flight snapshots finish, the device's peak memory is
read, the program's state is freed, and only then does the reference run.
With ``trace`` the profiler records a slice of the window; the per-layer
metrics are read from it and from the benchmark's own spans.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from harness import check, loop, peaks, spec, trace, traffic

#: closed-loop steps before the window opens (the first one compiles)
CLOSED_WARMUP_STEPS = 8
#: how long past the window's close a request may still retire
DRAIN_GRACE_S = 60.0
#: the traced slice: this share of the window, from a third of the way in
SLICE_SHARE = 1 / 3
SLICE_MIN_S, SLICE_MAX_S = 2.0, 4.0


@dataclasses.dataclass
class RunView:
    """What a metric reader reads (``metrics/<name>.py``: ``read(run)``)."""

    cell: spec.Cell
    setup_s: float
    #: the window's start and the end of its last step (clock seconds)
    t0: float
    t_end: float
    requests: List[loop.Request]
    #: (start, end, program's volleys served so far) per step
    steps: np.ndarray
    #: the traced slice on the clock, or None
    slice: Optional[Tuple[float, float]]
    #: the reduced profiler trace of the slice, or None
    trace: Optional[trace.Trace]
    peak: Dict[str, float]

    @property
    def config(self) -> Dict:
        return self.cell.config

    def slice_mask(self) -> np.ndarray:
        """Which steps lie wholly inside the traced slice."""
        if self.slice is None:
            return np.zeros(len(self.steps), bool)
        a, b = self.slice
        return (self.steps[:, 0] >= a) & (self.steps[:, 1] <= b)

    def slice_steps(self) -> np.ndarray:
        """(start, end, volleys served so far) of the slice's steps."""
        return self.steps[self.slice_mask()]

    def step_volleys(self) -> np.ndarray:
        """Volleys the program served in each step (its counter's steps)."""
        return np.diff(self.steps[:, 2], prepend=0.0)

    def window_requests(self) -> List[loop.Request]:
        return [r for r in self.requests if not r.warmup and r.due >= self.t0]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sequence."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q / 100 * len(v)) - 1)])


def _device() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak() -> int:
    peaks_ = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks_.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks_) if peaks_ else 0


class _GcPauses:
    """``gc.callbacks`` hook: (start, seconds) of each collection."""

    def __init__(self):
        self.pauses: List[Tuple[float, float]] = []
        self._t = 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((self._t, time.perf_counter() - self._t))


def _stalls(win: loop.Window, t0: float, gcp: _GcPauses) -> str:
    """Where the window lost time: its longest steps (wall and this
    thread's CPU), its longest gaps between steps, and the GC's pauses."""
    steps = np.asarray(win.steps, np.float64).reshape(-1, 3)
    if len(steps) < 2:
        return "stalls: too few steps"
    wall = steps[:, 1] - steps[:, 0]
    gap = steps[1:, 0] - steps[:-1, 1]
    top = np.argsort(wall)[-3:][::-1]
    topg = np.argsort(gap)[-3:][::-1]
    gcs = [d for _, d in gcp.pauses]
    return ("stalls: longest steps " + ", ".join(
        f"{1e3 * wall[i]:.1f} ms (cpu {1e3 * win.step_cpu[i]:.1f}) at "
        f"{steps[i, 0] - t0:.2f} s" for i in top)
        + "; longest gaps " + ", ".join(
            f"{1e3 * gap[i]:.1f} ms at {steps[i, 1] - t0:.2f} s" for i in topg)
        + f"; gc {len(gcs)} pauses, {1e3 * sum(gcs):.1f} ms, max "
        f"{1e3 * max(gcs, default=0.0):.1f} ms")


def _warmup(win: loop.Window, cell: spec.Cell, sched: traffic.Schedule
            ) -> None:
    if sched.due is None:
        win.start_closed()
        for _ in range(CLOSED_WARMUP_STEPS):
            for r in win.step():
                win.send_next(r.done)
        return
    now = win.clock()
    for i in range(int(cell.config["n_slots"])):
        win.send(2, i, now, warmup=True)
    win.drain(math.inf)


def run(cell: spec.Cell, seed: int, seconds: float, with_trace: bool,
        t_start: float, engine: Optional[Callable] = None,
        log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> Dict:
    """Run the cell once; returns the result line's object. ``engine``
    takes the place of the system's ``engine`` (the control runs)."""
    cfg, sut = cell.config, cell.system
    engine = engine or sut.engine
    marks = [("start", time.perf_counter())]
    pool = sut.pool(cfg, seed)
    marks.append(("pool", time.perf_counter()))
    w0 = sut.init_params(
        jax.random.PRNGKey(traffic.seed_key_int(seed, 3)), cfg)
    w0_host = jax.device_get(w0)
    marks.append(("weights", time.perf_counter()))
    eng = engine(w0, cfg, cell.name)
    marks.append(("engine", time.perf_counter()))
    sched = traffic.schedule(cell.traffic, seconds, seed)
    tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
    win = loop.Window(eng, pool, sched, payload=sut.payload,
                      record_schedule=sut.stateful(cfg),
                      trace_dir=tmp.name if with_trace else None)
    _warmup(win, cell, sched)
    t0 = win.clock()
    setup_s = t0 - t_start
    marks.append(("warm-up", t0))
    log(f"set-up {setup_s:.3f} s: jax start-up "
        f"{marks[0][1] - t_start:.3f} s, " + ", ".join(
            f"{name} {t - marks[i][1]:.3f} s"
            for i, (name, t) in enumerate(marks[1:])))
    if with_trace:
        length = min(SLICE_MAX_S, max(SLICE_MIN_S, SLICE_SHARE * seconds))
        a = t0 + SLICE_SHARE * seconds
        win.trace_between(a, a + length)
    gc_pauses = _GcPauses()
    gc.callbacks.append(gc_pauses)
    if sched.due is None:
        win.run_closed(t0 + seconds)
        t_end = win.steps[-1][1]
        win.stop_trace()
        win.drain(t_end + DRAIN_GRACE_S)
    else:
        win.run_open(t0, t0 + seconds + DRAIN_GRACE_S)
        t_end = win.steps[-1][1]
        win.stop_trace()
    gc.callbacks.remove(gc_pauses)
    log(_stalls(win, t0, gc_pauses))
    eng.close()
    memory_peak = _memory_peak()
    w_final = eng.final_state()
    n_snapshots = eng.n_snapshots
    win.eng = None          # free the program's state before the reference
    del eng, w0
    gc.collect()

    reduced = None
    if with_trace:
        reduced = trace.load(tmp.name)
    tmp.cleanup()
    view = RunView(cell=cell, setup_s=setup_s, t0=t0, t_end=t_end,
                   requests=win.requests,
                   steps=np.asarray(win.steps, np.float64).reshape(-1, 3),
                   slice=win.slice, trace=reduced,
                   peak=peaks.peak(jax.devices()[0].device_kind)
                   if jax.devices()[0].platform == "tpu" else {})
    metrics_list = cell.per_layer if with_trace else cell.end_to_end
    metrics = {}
    for m in metrics_list:
        value = spec.load_reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_check = time.perf_counter()
    numbers = sut.compare(cfg, pool, w0_host, win.requests, win.served,
                          w_final)
    correct, compared = check.held(numbers, cfg["check"]["limits"])
    log(f"check {time.perf_counter() - t_check:.3f} s; numbers {numbers}; "
        f"steps {len(win.steps)}; snapshots {n_snapshots}")

    attempted = view.window_requests()
    device = _device()
    device["memory_peak_bytes"] = memory_peak
    result = {
        "correct": bool(correct),
        "attempted": len(attempted),
        "failed": sum(1 for r in attempted if r.out is None),
        "metrics": metrics,
        "device": device,
    }
    if with_trace and reduced is not None:
        w = trace.window(reduced)
        if w is not None:
            a, b, _ = w
            result["device"]["busy_s"] = trace.busy_ns(reduced, a, b) / 1e9
            result["device"]["window_s"] = (b - a) / 1e9
            result["breakdown"] = {
                "device_ops": trace.op_totals(reduced, a, b),
                "idle_gaps": trace.idle_gaps(reduced, a, b)}
    result["readings"] = numbers
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, v, lim in compared}
    for name, v, lim in compared:
        log(f"compared {name} {v!r} limit {lim!r}")
    return result
