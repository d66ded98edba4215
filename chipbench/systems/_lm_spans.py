"""Device time of the LM engine's launches, from the program's spans.

``serve/engine.Engine.step`` runs inside an ``lm.step`` span, launches each
prefill chunk inside an ``lm.prefill`` span and the decode step inside an
``lm.decode`` span, and ends by fetching every launch's outputs (``lm.fetch``).
One TPU stream runs a step's launches in order and the fetch waits for the
last, so all of a step's device work lies inside its ``lm.step`` span, and
none of another step's does. Each step's device busy time is the union of
the first device's operation intervals inside the span (``harness/trace``);
it is split among the step's launches:

  decode   the mean busy time of the steps that launched one decode step
           and no prefill chunk
  prefill  over the steps that launched prefill chunks, the mean of (busy
           time - decode time x decode launches) / chunks

A trace without ``lm.step`` spans, or without steps of a kind, gives None.
"""

from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from harness import trace

STEP, PREFILL, DECODE = "lm.step", "lm.prefill", "lm.decode"


def steps(tr: Optional[trace.Trace]) -> List[Tuple[float, int, int]]:
    """(device busy ns, prefill chunks, decode launches) of each ``lm.step``
    inside the traced window."""
    w = None if tr is None else trace.window(tr)
    dev = None if w is None else next((d for d in tr.devices if d), None)
    if dev is None:
        return []
    a, b, _ = w
    ops = sorted((s, s + d) for _, s, d, _ in dev)
    starts = [s for s, _ in ops]
    longest = max((e - s for s, e in ops), default=0.0)
    spans = sorted((s, n) for n, s, _, _ in tr.host
                   if n in (PREFILL, DECODE))
    span_starts = [s for s, _ in spans]
    out = []
    for name, s, d, _ in tr.host:
        if name != STEP or s < a or s + d > b:
            continue
        inside = [n for _, n in spans[bisect.bisect_left(span_starts, s):
                                      bisect.bisect_right(span_starts, s + d)]]
        near = ops[bisect.bisect_left(starts, s - longest):
                   bisect.bisect_right(starts, s + d)]
        busy = sum(e - x for x, e in trace.union(near, s, s + d))
        out.append((busy, inside.count(PREFILL), inside.count(DECODE)))
    return out


def decode_ms(tr: Optional[trace.Trace]) -> Optional[float]:
    """Device time of one decode launch, in ms (module docstring)."""
    busy = [ns for ns, p, dec in steps(tr) if p == 0 and dec == 1]
    return sum(busy) / len(busy) / 1e6 if busy else None


def prefill_ms(tr: Optional[trace.Trace]) -> Optional[float]:
    """Device time of one prefill chunk, in ms (module docstring)."""
    dec = decode_ms(tr)
    rows = [(ns, p, d) for ns, p, d in steps(tr) if p > 0]
    if not rows or (dec is None and any(d for _, _, d in rows)):
        return None
    per = [(ns / 1e6 - (dec or 0.0) * d) / p for ns, p, d in rows]
    return sum(per) / len(per)
