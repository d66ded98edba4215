"""The comparison that decides ``correct``.

What the timed path returned is compared with the plain reference
(``reference.py``), which is given only the configuration, the seed's
weights and volleys, and the order in which the window served the volleys:

  * feedforward cells: each pool volley goes once through the reference;
    every volley every request was served (warm-up, window and drain) is
    compared with the reference's output for it, spike time for spike time.
  * learning cells: the reference serves the recorded schedule step by step
    from the seed's weights, learning as it goes, and every served volley is
    compared with the reference's output at that step; so are the final
    weights.

Each number is held against its limit from the configuration's ``check``
block; a request that never came back counts under ``lost_requests``.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Sequence, Tuple

import numpy as np

from harness import reference


def _served_rows(requests, served: Sequence[Sequence[Tuple[int, int]]],
                 n_slots: int, pool_size: int):
    """Batch rows the reference serves: a stream takes the lowest free row
    when its first volley is served and keeps it until its last one (the
    slot pool's FIFO lowest-free-slot rule). Returns rows (S, B) of pool
    indices (-1 empty), fresh (S, B), and per request its (step, row) list."""
    n = len(served)
    rows = np.full((n, n_slots), -1, np.int32)
    fresh = np.zeros((n, n_slots), bool)
    where: Dict[int, List[Tuple[int, int]]] = {}
    free = list(range(n_slots))
    row_of: Dict[int, int] = {}
    for s, pairs in enumerate(served):
        for ri, j in pairs:
            if j == 0:
                if not free:
                    raise ValueError(f"step {s} serves more than {n_slots} "
                                     "streams")
                row_of[ri] = heapq.heappop(free)
            row = row_of[ri]
            r = requests[ri]
            rows[s, row] = (r.offset + j) % pool_size
            fresh[s, row] = j == 0
            where.setdefault(ri, []).append((s, row))
        for ri, j in pairs:
            if j == requests[ri].length - 1:
                heapq.heappush(free, row_of.pop(ri))
    return rows, fresh, where


def compare(config: Dict, pool: np.ndarray, w0: Sequence[np.ndarray],
            requests, served, w_final) -> Dict[str, float]:
    """The numbers compared, by name (see the module docstring)."""
    spec = reference.net_spec(config)
    lost = sum(1 for r in requests if r.out is None)
    done = [r for r in requests if r.out is not None]
    mismatched = 0
    numbers: Dict[str, float] = {}
    if not spec.learn and not any(lay.recurrent for lay in spec.layers):
        ref = reference.pool_outputs(w0, pool, spec)
        for r in done:
            idx = (r.offset + np.arange(r.length)) % pool.shape[0]
            mismatched += int(np.sum(np.any(r.out != ref[idx], axis=(1, 2))))
    else:
        rows, fresh, where = _served_rows(requests, served,
                                          int(config["n_slots"]),
                                          pool.shape[0])
        outs, w_ref = reference.replay(w0, pool, rows, fresh, spec)
        for r in done:
            at = where.get(r.idx, [])
            if len(at) != r.length:
                mismatched += r.length
                continue
            ref = outs[[s for s, _ in at], [row for _, row in at]]
            mismatched += int(np.sum(np.any(r.out != ref, axis=(1, 2))))
        if spec.learn:
            numbers["weights_differing"] = float(sum(
                int(np.sum(p != q)) for p, q in zip(w_final, w_ref)))
    numbers["mismatched_volleys"] = float(mismatched)
    numbers["lost_requests"] = float(lost)
    return numbers


def held(numbers: Dict[str, float], limits: Dict[str, float]
         ) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """(every number within its limit, [(name, number, limit), ...]) for the
    numbers the configuration holds."""
    rows = [(name, numbers[name], float(limit))
            for name, limit in limits.items()]
    return all(v <= lim for _, v, lim in rows), rows
