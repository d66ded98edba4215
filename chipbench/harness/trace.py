"""Reduction of a profiler trace to device busy time, kernel time and gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps two
things, both on the trace's own clock (nanoseconds):

  devices  per TPU device, the operations on its "XLA Ops" line:
           (name, start, duration, label): the name is the HLO instruction's
           (``rnl_fire_times_layer.2``: a ``pallas_call`` is named after the
           function that makes it), the label the event's whole text;
  host     the events of the host thread that ran the benchmark's own spans
           (``chipbench.step`` ...), with the runtime's events nested in them.

The traced window runs from the start of the first ``chipbench.step`` span to
the end of the last one. Everything below is measured inside it: busy time is
the union of the device's operation intervals (averaged over devices), a
kernel's time the sum of its events' durations, and each idle gap between
device operations is split among the innermost host events that cover it
(with the profiler's Python tracer on, the Python functions that kept the
host busy).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: the device planes of a TPU trace, and the line that holds its operations
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
STEP_SPAN = "chipbench.step"

Event = Tuple[str, float, float, str]      # name, start ns, duration ns, label


@dataclasses.dataclass
class Trace:
    devices: List[List[Event]]
    host: List[Event]

    @classmethod
    def from_json(cls, obj: Dict) -> "Trace":
        return cls([[tuple(e) for e in d] for d in obj["devices"]],
                   [tuple(e) for e in obj["host"]])


def _op_name(text: str) -> str:
    """``%rnl_fire_times_layer.2 = s32[...] custom-call(...)`` ->
    ``rnl_fire_times_layer.2``."""
    return text.split(" = ", 1)[0].lstrip("%")


def xplane_file(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or the newest one under a log dir)."""
    import jax

    if os.path.isdir(path):
        path = xplane_file(path)
    data = jax.profiler.ProfileData.from_file(path)
    devices: List[List[Event]] = []
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(_op_name(e.name), float(e.start_ns),
                                     float(e.duration_ns), e.name)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, float(e.start_ns), float(e.duration_ns),
                           "") for e in line.events]
                if any(e[0] == STEP_SPAN for e in events):
                    host.extend(events)
    return Trace(devices, host)


def window(trace: Trace) -> Optional[Tuple[float, float, int]]:
    """(start, end, steps): the span of the traced ``chipbench.step`` spans."""
    steps = [(s, s + d) for n, s, d, _ in trace.host if n == STEP_SPAN]
    if not steps:
        return None
    return min(s for s, _ in steps), max(e for _, e in steps), len(steps)


def _clip(intervals: Sequence[Tuple[float, float]], a: float, b: float):
    out = [(max(s, a), min(e, b)) for s, e in intervals]
    return sorted((s, e) for s, e in out if e > s)


def union(intervals: Sequence[Tuple[float, float]], a: float, b: float
          ) -> List[Tuple[float, float]]:
    """Disjoint union of the intervals, clipped to [a, b]."""
    merged: List[List[float]] = []
    for s, e in _clip(intervals, a, b):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(trace: Trace, a: float, b: float) -> float:
    """Device time with an operation running, averaged over the devices
    that ran any operation."""
    per = [sum(e - s for s, e in union([(s, s + d) for _, s, d, _ in dev],
                                       a, b))
           for dev in trace.devices if dev]
    return sum(per) / len(per) if per else 0.0


def kernel_ns(trace: Trace, pattern: str, a: float, b: float) -> float:
    """Summed durations of the operations whose name or label matches
    ``pattern``, inside [a, b], averaged over the devices that ran any."""
    rx = re.compile(pattern)
    per = [sum(d for n, s, d, lab in dev
               if a <= s and s + d <= b and (rx.search(n) or rx.search(lab)))
           for dev in trace.devices if dev]
    return sum(per) / len(per) if per else 0.0


def op_totals(trace: Trace, a: float, b: float, top: int = 10
              ) -> List[List]:
    """The operations that took most device time: [[name, seconds], ...]."""
    tot: Dict[str, float] = {}
    for dev in trace.devices:
        for n, s, d, _ in dev:
            if a <= s and s + d <= b:
                tot[n] = tot.get(n, 0.0) + d
    n_dev = max(1, sum(1 for dev in trace.devices if dev))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / n_dev / 1e9] for name, ns in ranked]


def gaps(trace: Trace, a: float, b: float) -> List[Tuple[float, float]]:
    """Idle intervals of the first device that ran operations, in [a, b]."""
    dev = next((d for d in trace.devices if d), None)
    if dev is None:
        return []
    busy = union([(s, s + d) for _, s, d, _ in dev], a, b)
    out, t = [], a
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if b > t:
        out.append((t, b))
    return out


#: host events shorter than this are folded into the event that holds them
HOST_MIN_NS = 0.5e6


def idle_gaps(trace: Trace, a: float, b: float, top: int = 10) -> List[List]:
    """Device idle time by what the host was doing, longest first:
    [[host activity, seconds], ...]. Each instant of each gap goes to the
    innermost host event of at least ``HOST_MIN_NS`` that covers it (with the
    profiler's Python tracer on, the Python function that kept the host)."""
    spans = sorted((s, s + d, n) for n, s, d, _ in trace.host
                   if d >= HOST_MIN_NS and s < b and s + d > a)
    starts = [sp[0] for sp in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    tot: Dict[str, float] = {}
    for g0, g1 in gaps(trace, a, b):
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_right(starts, g1)
        cover = [sp for sp in spans[lo:hi] if sp[0] < g1 and sp[1] > g0]
        cuts = sorted({g0, g1} | {max(g0, s) for s, _, _ in cover}
                      | {min(g1, e) for _, e, _ in cover})
        for x0, x1 in zip(cuts, cuts[1:]):
            inner = min(((e - s, n) for s, e, n in cover
                         if s <= x0 and e >= x1), default=None)
            name = inner[1] if inner else "no host span"
            tot[name] = tot.get(name, 0.0) + (x1 - x0)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]

