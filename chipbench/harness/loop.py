"""The measured window: the traffic driven through the program's serve loop.

One thread does what ``AsyncTNNEngine._pump`` does for real clients: before
every ``step()`` it sends every request that is due, and it hands each retired
request's result back at once. Every request keeps its own clock readings:

  due      when it was due to be sent (open loop: the schedule; closed loop:
           when its client's previous request retired)
  sent     when ``submit`` returned
  started  start of the first step that served one of its volleys
  done     end of the step that retired it (its result is then in hand)

A request's input is the system's ``payload(pool, offset, length)``. Each
step keeps (start, end, the program's count of units served). With
``trace_dir`` set, the profiler records a slice of the window, and the
benchmark's own calls (``submit``, ``step``, waiting for the next arrival)
show in it as host spans named ``chipbench.<call>``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from harness import traffic


@dataclasses.dataclass
class Request:
    idx: int
    length: int
    offset: int
    due: float
    warmup: bool = False
    sent: float = math.nan
    started: float = math.nan
    done: float = math.nan
    out: Optional[np.ndarray] = None


class Window:
    def __init__(self, engine, pool, sched: traffic.Schedule, *,
                 payload: Callable, record_schedule: bool = False,
                 trace_dir: Optional[str] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.eng = engine
        self.pool = pool
        self._payload = payload
        self.sched = sched
        self.clock = clock
        self.requests: List[Request] = []
        #: (start, end, program's units served so far) per step
        self.steps: List[Tuple[float, float, int]] = []
        #: this thread's CPU seconds in each step (a step far longer than
        #: its CPU time waited: on the device, on I/O, or descheduled)
        self.step_cpu: List[float] = []
        #: per step, the (request idx, unit idx) pairs it served, in the
        #: order the requests were sent (``record_schedule`` only)
        self.served: List[List[Tuple[int, int]]] = []
        self._record = record_schedule
        self._inflight: Dict[int, Tuple[Request, object, List[int]]] = {}
        self._unstarted: Dict[int, Tuple[Request, object]] = {}
        self._next = 0
        self._trace_dir = trace_dir
        self._slice_at: Optional[Tuple[float, float]] = None
        #: clock readings when the traced slice began and ended
        self.slice: Optional[Tuple[float, float]] = None
        self._tracing = False

    # ------------------------------------------------------------ spans
    def _span(self, name: str):
        if self._trace_dir is None:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    def trace_between(self, a: float, b: float) -> None:
        """Trace the slice of the window from clock ``a`` to ``b``."""
        self._slice_at = (a, b)

    def _maybe_trace(self, now: float) -> None:
        if self._slice_at is None or self._trace_dir is None:
            return
        a, b = self._slice_at
        if not self._tracing and self.slice is None and now >= a:
            jax.profiler.start_trace(self._trace_dir)
            self._tracing = True
            self.slice = (self.clock(), math.nan)
        elif self._tracing and now >= b:
            self.slice = (self.slice[0], self.clock())
            jax.profiler.stop_trace()
            self._tracing = False

    def stop_trace(self) -> None:
        """End the slice if the window closed before it did."""
        if self._tracing:
            self.slice = (self.slice[0], self.clock())
            jax.profiler.stop_trace()
            self._tracing = False

    # ------------------------------------------------------- send / step
    def send(self, length: int, offset: int, due: float,
             warmup: bool = False) -> Request:
        r = Request(len(self.requests), int(length), int(offset), due, warmup)
        payload = self._payload(self.pool, r.offset, r.length)
        with self._span("submit"):
            h = self.eng.submit(payload)
        r.sent = self.clock()
        self.requests.append(r)
        self._inflight[h.req_id] = (r, h, [0])
        self._unstarted[h.req_id] = (r, h)
        return r

    def send_next(self, due: float) -> Request:
        """Send the schedule's next request."""
        k = self._next % len(self.sched.lengths)
        self._next += 1
        return self.send(self.sched.lengths[k], self.sched.offsets[k], due)

    def step(self) -> List[Request]:
        """One engine step; returns the requests it retired."""
        with self._span("step"):
            cpu = time.thread_time()
            ts = self.clock()
            retired = self.eng.step()
            te = self.clock()
            cpu = time.thread_time() - cpu
        self.steps.append((ts, te, int(self.eng.n_volleys)))
        self.step_cpu.append(cpu)
        for rid in [rid for rid, (_, h) in self._unstarted.items()
                    if h.outputs]:
            self._unstarted.pop(rid)[0].started = ts
        if self._record:
            served = []
            for r, h, seen in self._inflight.values():
                n = len(h.outputs)
                served.extend((r.idx, j) for j in range(seen[0], n))
                seen[0] = n
            self.served.append(served)
        done = []
        for h in retired:
            r = self._inflight.pop(h.req_id)[0]
            r.done = te
            r.out = h.result()
            done.append(r)
        return done

    # ------------------------------------------------------------ loops
    def run_closed(self, t_stop: float) -> None:
        """Closed loop until ``t_stop``: every retirement sends its client's
        next request at once (the clients were started by ``start_closed``)."""
        while True:
            now = self.clock()
            self._maybe_trace(now)
            if now >= t_stop:
                return
            for r in self.step():
                self.send_next(r.done)

    def start_closed(self) -> None:
        now = self.clock()
        for _ in range(self.sched.clients):
            self.send_next(now)

    def run_open(self, t0: float, t_limit: float) -> None:
        """Open loop: the schedule's requests sent when due (from ``t0``),
        stepping while there is work, until every request has retired or
        the clock passes ``t_limit``."""
        due = t0 + self.sched.due
        i, n = 0, len(due)
        while True:
            now = self.clock()
            self._maybe_trace(now)
            if now >= t_limit:
                return
            while i < n and due[i] <= now:
                self.send(self.sched.lengths[i], self.sched.offsets[i],
                          due[i])
                i += 1
            if self.eng.has_work:
                self.step()
            elif i < n:
                self._wait_until(due[i])
            else:
                return

    def drain(self, t_limit: float) -> None:
        """Step until nothing is in flight or the clock passes ``t_limit``."""
        while self.eng.has_work and self.clock() < t_limit:
            self.step()

    def _wait_until(self, t: float) -> None:
        with self._span("wait"):
            while self.clock() < t - 2e-3:
                time.sleep(1e-3)
            while self.clock() < t:
                pass
