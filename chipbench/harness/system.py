"""The system under test: the program's TNN serve engine, built from a
configuration file. The only module of the benchmark that imports the program.

``ProgramEngine`` is the engine of ``systems/tnn.py``: ``submit(volleys) ->
handle``, ``step() -> retired handles``, ``has_work``, ``n_volleys`` (the
program's count of volleys served), ``close()``, ``n_snapshots`` and
``final_state()`` (the learned weights, or None). A handle has ``req_id``,
``outputs`` (one (C, Q) array per served volley, growing as the stream is
served) and ``result()``. ``ControlEngine`` offers the same interface over
the reference, for the control runs.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import shutil
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference, spec


def _network(config: Dict):
    from repro.core import layer, network, stdp

    st = config.get("stdp", {})
    stdp_cfg = stdp.STDPConfig(
        w_max=int(config["w_max"]),
        mu_capture=float(st.get("mu_capture", 1.0)),
        mu_backoff=float(st.get("mu_backoff", 0.5)),
        mu_search=float(st.get("mu_search", 0.25)),
        stabilize=bool(st.get("stabilize", True)),
        stab_floor=float(st.get("stab_floor", 0.25)))
    layers = [layer.TNNLayer(
        n_columns=int(lay["n_columns"]), rf_size=int(lay["rf_size"]),
        rf_stride=lay.get("rf_stride"), n_neurons=int(lay["n_neurons"]),
        threshold=int(lay["threshold"]), t_steps=int(config["t_steps"]),
        dendrite=config["dendrite"], k=int(config["k"]),
        w_max=int(config["w_max"]), recurrent=bool(lay.get("recurrent")),
        backend=config["backend"], stdp=stdp_cfg,
        stdp_reduction=st.get("reduction", "mean"))
        for lay in config["layers"]]
    return network.make_network(layers)


def checkpoint_dir(cell: str) -> pathlib.Path:
    """Where a learning cell's snapshots go: a fixed path in the checkout."""
    return spec.ROOT / ".chipbench" / "ckpt" / cell


class ProgramEngine:
    """``serve.tnn_engine.TNNEngine`` as the configuration states it."""

    def __init__(self, params, config: Dict, cell: str):
        from repro.serve import tnn_engine

        ckpt = None
        if config.get("checkpoint_every"):
            ckpt = checkpoint_dir(cell)
            shutil.rmtree(ckpt, ignore_errors=True)
            ckpt.mkdir(parents=True)
        scfg = tnn_engine.TNNServeConfig(
            n_slots=int(config["n_slots"]), backend=config["backend"],
            learn=bool(config.get("learn", False)),
            stdp_every=int(config.get("stdp_every", 1)),
            stdp_seed=None,  # the deterministic expectation rule
            checkpoint_dir=None if ckpt is None else str(ckpt),
            checkpoint_every=int(config.get("checkpoint_every", 0)),
            checkpoint_keep=int(config.get("checkpoint_keep", 3)))
        self._ckpt = ckpt
        self._learn = scfg.learn
        self._eng = tnn_engine.TNNEngine(params, _network(config), scfg)

    def submit(self, volleys: np.ndarray):
        return self._eng.submit(volleys)

    def step(self) -> List:
        return self._eng.step()

    @property
    def has_work(self) -> bool:
        return self._eng.pool.has_work

    @property
    def n_volleys(self) -> int:
        return self._eng.n_volleys

    @property
    def n_snapshots(self) -> int:
        return self._eng.n_snapshots

    def final_state(self) -> Optional[List[np.ndarray]]:
        """The weights after serving, when the configuration learns."""
        if not self._learn:
            return None
        return [np.asarray(p) for p in self._eng.params]

    def close(self) -> None:
        """Wait for in-flight snapshots, then remove them (they are written
        to be timed, not kept)."""
        self._eng.checkpoint_wait()
        if self._ckpt is not None:
            shutil.rmtree(self._ckpt, ignore_errors=True)


@dataclasses.dataclass
class _Handle:
    req_id: int
    volleys: np.ndarray
    outputs: list = dataclasses.field(default_factory=list)

    def result(self) -> np.ndarray:
        return np.stack(self.outputs)


class ControlEngine:
    """The reference in the program's place, weights held in ``wdtype``.

    Same slot semantics as the program's engine (FIFO admission into the
    lowest free slot at the top of a step, retirement the moment a stream
    ends), one jitted reference step over all ``n_slots`` rows per step.
    """

    def __init__(self, params, config: Dict, wdtype=jnp.bfloat16):
        self._spec = reference.net_spec(config)
        self._n = int(config["n_slots"])
        self.params = reference.in_dtype(params, wdtype)
        self._slots: List[Optional[_Handle]] = [None] * self._n
        self._carry: List[Optional[tuple]] = [None] * self._n
        self._pending: collections.deque = collections.deque()
        self._next = 0
        self.n_volleys = 0
        self.n_snapshots = 0
        self._step = jax.jit(lambda p, x, c: reference.step(
            p, x, c, self._spec, wdtype))

    def submit(self, volleys: np.ndarray) -> _Handle:
        h = _Handle(self._next, np.asarray(volleys, np.int32))
        self._next += 1
        self._pending.append(h)
        return h

    @property
    def has_work(self) -> bool:
        return bool(self._pending) or any(s is not None for s in self._slots)

    def step(self) -> List[_Handle]:
        for i in range(self._n):
            if self._slots[i] is None and self._pending:
                self._slots[i] = self._pending.popleft()
                self._carry[i] = None
        x = np.full((self._n, self._n_inputs()), reference.NO_SPIKE, np.int32)
        carry = [np.full((self._n, lay.n_outputs), reference.NO_SPIKE,
                         np.int32) if lay.recurrent else None
                 for lay in self._spec.layers]
        live = [i for i, s in enumerate(self._slots) if s is not None]
        for i in live:
            h = self._slots[i]
            x[i] = h.volleys[len(h.outputs)]
            if self._carry[i] is not None:
                for c, ci in zip(carry, self._carry[i]):
                    if c is not None:
                        c[i] = ci
        out, c_next, self.params = self._step(
            self.params, jnp.asarray(x),
            tuple(None if c is None else jnp.asarray(c) for c in carry))
        out = np.asarray(out)
        c_next = [None if c is None else np.asarray(c) for c in c_next]
        retired = []
        for i in live:
            h = self._slots[i]
            h.outputs.append(out[i].copy())
            self._carry[i] = tuple(None if c is None else c[i] for c in c_next)
            if len(h.outputs) == h.volleys.shape[0]:
                self._slots[i] = None
                retired.append(h)
        self.n_volleys += len(live)
        return retired

    def _n_inputs(self) -> int:
        lay = self._spec.layers[0]
        return lay.stride * (lay.n_columns - 1) + lay.rf_size

    def final_state(self) -> Optional[List[np.ndarray]]:
        if not self._spec.learn:
            return None
        return [np.asarray(p) for p in self.params]

    def close(self) -> None:
        pass
