"""Device idle time split among the program's own host spans.

The serve engine runs each ``step()`` inside a ``tnn.step`` span and each of
its phases inside a ``tnn.<phase>`` span (``serve/tnn_engine.py``). They are
written on the thread that runs the benchmark's ``chipbench.step`` spans, so
``trace.load`` keeps them, on the device trace's clock.

``idle_split`` takes the first device's idle intervals inside the traced
window (``trace.window``, ``trace.gaps``), intersects them with the union of
the spans of each part, and gives each part's idle time per step:

  measure   ``tnn.measure``: the policy's density and receptive-field counts
  transfer  ``tnn.h2d``, ``tnn.fetch``: placing the batch, waiting on outputs
  host      the rest of ``tnn.step``: admit, batch, resolve, dispatch,
            retire, snapshot, and the step's own lines between its spans
  outside   no ``tnn.step`` span: the caller's loop, ``submit``, open-loop
            waits, collections

The four parts partition the idle time, so they add up to it. A trace with
no ``tnn.step`` span (a program without the spans) gives ``None``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace

STEP = "tnn.step"
SNAPSHOT = "tnn.snapshot"
MEASURE = ("tnn.measure",)
TRANSFER = ("tnn.h2d", "tnn.fetch")

Intervals = List[Tuple[float, float]]


def _spans(tr: trace.Trace, names: Sequence[str], a: float, b: float
           ) -> Intervals:
    return trace.union([(s, s + d) for n, s, d, _ in tr.host if n in names],
                       a, b)


def _intersect(x: Intervals, y: Intervals) -> Intervals:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        s, e = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if e > s:
            out.append((s, e))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def _ns(x: Intervals) -> float:
    return sum(e - s for s, e in x)


def idle_split(tr: Optional[trace.Trace]) -> Optional[Dict[str, float]]:
    """Device idle milliseconds per step under each part (module docstring):
    ``{"host", "measure", "transfer", "outside"}``, or None."""
    if tr is None or not any(tr.devices) \
            or not any(n == STEP for n, _, _, _ in tr.host):
        return None
    w = trace.window(tr)
    if w is None:
        return None
    a, b, n = w
    idle = trace.gaps(tr, a, b)
    in_step = _intersect(idle, _spans(tr, (STEP,), a, b))
    measure = _ns(_intersect(in_step, _spans(tr, MEASURE, a, b)))
    transfer = _ns(_intersect(in_step, _spans(tr, TRANSFER, a, b)))
    parts = {"host": _ns(in_step) - measure - transfer, "measure": measure,
             "transfer": transfer, "outside": _ns(idle) - _ns(in_step)}
    return {k: v / 1e6 / n for k, v in parts.items()}


def snapshot_ms(tr: Optional[trace.Trace]) -> Optional[float]:
    """Mean duration in ms of the ``tnn.snapshot`` spans that start inside
    the traced window, or None if it holds none."""
    w = None if tr is None else trace.window(tr)
    if w is None:
        return None
    a, b, _ = w
    durs = [d for n, s, d, _ in tr.host if n == SNAPSHOT and a <= s <= b]
    return sum(durs) / len(durs) / 1e6 if durs else None
