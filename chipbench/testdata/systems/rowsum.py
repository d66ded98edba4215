"""A toy system for the harness's tests, found only through the test's own
search path: a request is a run of integer rows of the pool, and the engine
answers each row with its weighted sum, one row of every live request a
step, on ``n_slots`` slots (``systems/__init__.py`` has the contract)."""

import collections
from typing import Dict, List

import jax
import numpy as np

from harness import traffic

payload = traffic.stream


def pool(config: Dict, seed: int) -> np.ndarray:
    return traffic.seed_rng(seed, 1).integers(
        0, 100, (traffic.POOL_VOLLEYS, int(config["width"])))


def init_params(key, config: Dict) -> jax.Array:
    return jax.random.randint(key, (int(config["width"]),), 0, 10)


class _Handle:
    def __init__(self, req_id: int, rows: np.ndarray):
        self.req_id = req_id
        self.rows = rows
        self.outputs: List = []

    def result(self) -> np.ndarray:
        return np.asarray(self.outputs)


class Engine:
    """FIFO admission into free slots at the top of a step; a request
    retires in the step that answers its last row."""

    def __init__(self, params, config: Dict, dtype=np.int64):
        self._w = np.asarray(params).astype(dtype)
        self._dtype = dtype
        self._slots = [None] * int(config["n_slots"])
        self._pending: collections.deque = collections.deque()
        self._next = 0
        self.n_volleys = 0
        self.n_snapshots = 0

    def submit(self, rows: np.ndarray) -> _Handle:
        h = _Handle(self._next, np.asarray(rows))
        self._next += 1
        self._pending.append(h)
        return h

    @property
    def has_work(self) -> bool:
        return bool(self._pending) or any(s is not None for s in self._slots)

    def step(self) -> List[_Handle]:
        for i, s in enumerate(self._slots):
            if s is None and self._pending:
                self._slots[i] = self._pending.popleft()
        retired = []
        for i, h in enumerate(self._slots):
            if h is None:
                continue
            h.outputs.append(h.rows[len(h.outputs)].astype(self._dtype)
                             @ self._w)
            self.n_volleys += 1
            if len(h.outputs) == len(h.rows):
                self._slots[i] = None
                retired.append(h)
        return retired

    def close(self) -> None:
        pass

    def final_state(self) -> None:
        return None


def engine(params, config: Dict, cell: str) -> Engine:
    return Engine(params, config)


def control_engine(params, config: Dict) -> Engine:
    """The sums in float16, below the integers the program adds in."""
    return Engine(params, config, np.float16)


def stateful(config: Dict) -> bool:
    return False


def compare(config: Dict, pool: np.ndarray, params_host, requests, served,
            final_state) -> Dict[str, float]:
    ref = pool @ np.asarray(params_host, np.int64)
    done = [r for r in requests if r.out is not None]
    mismatched = sum(
        int(np.sum(r.out != ref[(r.offset + np.arange(r.length))
                                % pool.shape[0]])) for r in done)
    return {"mismatched_rows": float(mismatched),
            "lost_requests": float(len(requests) - len(done))}
