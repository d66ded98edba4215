"""Slot-based TNN inference engine: volley batching over decode-style slots.

Serves TNN inference to many concurrent clients the way the LM engine serves
decode tokens (DESIGN.md §5.3). A *request* is a client's stream of encoded
spike volleys (``core/coding.py``: ``value_to_time`` / ``grf_encode``), one
volley per gamma cycle. Requests are admitted into a fixed pool of B slots
(:class:`repro.serve.slots.SlotPool`); each engine step stacks the live slots'
next volleys into the ``(B, n_inputs)`` batch that ``TNNLayer``/``TNNNetwork``
already vectorize over, runs one jit-compiled ``network.forward`` — every
neuron evaluated through the backend-dispatched ``fire_times_bank`` (scan /
closed_form / event / pallas / auto) — and scatters the ``(B, C, Q)`` output
spike times back to the slots. A request retires the moment its stream is
exhausted; its slot re-fills from the pending queue at the top of the next
step. No barrier on the slowest request.

Stateful streams live IN their slots (DESIGN.md §5.1): when the network has
recurrent layers, each slot's :class:`~repro.serve.slots.SlotEntry` ``state``
holds that stream's per-layer recurrent carry — initialised all-silent by the
pool's ``on_admit`` hook, gathered into per-layer ``(B, n_outputs)`` carry
batches each step (free rows stay silent, i.e. inert), threaded through
``network.forward(..., carry=...)``, and scattered back after the cycle. Two
streams sharing a batch never see each other's state — row r's carry is
row r's previous output, so slot outputs stay bit-exact against an unbatched
per-stream reference regardless of batch composition or mid-flight refill
churn. ``retire`` hands the final carry back on the entry
(``TNNRequest.final_state``), so a stream can be resubmitted later to
continue where it left off.

The neuron-bank engine is chosen once, at construction, inside the
engine's mesh scope (:func:`repro.core.policy.resolve_engine`): ``auto``
is the fused Pallas kernel on a TPU wherever every layer's column stack
can run it, the closed form elsewhere; an explicit backend runs as named.
The result is ``TNNEngine.engine``, and the network's ``auto`` layers are
pinned to it (explicit per-layer backends are respected). A step measures
its batch only for an explicit sparse engine (``event``,
``pallas_compact``): the max active lines over layer 0's receptive
fields, bucketed (``compaction.bucket_width``), compiles the stack with
static per-layer compaction widths (``network.sparse_widths``: measured
bucket for layer 0, the 1-WTA structural bound for deeper layers) — so the
jitted solve sorts ``2s`` breakpoints, not ``2n``. The lane-aligned bucket
ladder keeps the distinct widths, hence the jit variants, few
(``stats()['steps_measured']`` counts the measured steps,
``stats()['jit_variants']`` the variants). All engines are bit-exact, so
the choice is invisible in the outputs.

Empty slots carry all-``NO_SPIKE`` volleys: silent lines never fire a neuron,
so padding rows are inert, and the batch shape stays static — one XLA
compilation per (B, network) pair. Everything is int32 end to end, so engine
outputs are bit-exact against unbatched per-request ``network.forward`` calls
regardless of batch composition (pinned by tests/test_serve_tnn.py).

Learn while serving (DESIGN.md §5.5): behind ``TNNServeConfig(learn=True)``
the engine applies per-gamma-cycle layer-local STDP to the live slot batch —
every ``stdp_every`` steps the jitted step runs ``network.step`` (forward +
minibatch STDP, carry threaded) instead of ``network.forward`` and the
engine's weights advance; weights are explicit jit arguments throughout, so
a learning step never recompiles and, under a mesh, the updated stacks stay
column-sharded (``layer_step`` pins them via ``specs.tnn_param_axes``).
Free-slot padding rows are inert for learning exactly as they are for
inference (no input spike -> zero STDP delta). Durability: with
``checkpoint_dir``/``checkpoint_every`` set, the engine snapshots
``(weights, step counter, n_stdp_updates)`` through
``train/checkpoint.py``'s :class:`CheckpointManager` (async saves off the
serve thread), ``TNNEngine(..., resume=True)`` restores the latest snapshot
at construction, and :func:`serve_resilient` is the ``run_resilient``-style
serve driver: on an (injected) ``WorkerFailure`` it rolls the engine back to
the last snapshot and replays the streams not yet committed — exactly-once
per retired stream, bit-exact retired outputs with learning off. Learning
auto-pauses under admission pressure (queue-depth / step-latency
thresholds) and resumes when pressure clears; ``stats()`` reports
``n_stdp_updates`` / ``n_snapshots`` / ``n_restores`` /
``learning_paused`` and per-layer weight-drift norms.

Front doors:

* :meth:`TNNEngine.serve` — synchronous: submit a list of volley streams,
  drain the pool, get results in submission order.
* :class:`AsyncTNNEngine` — ``asyncio``: concurrent clients ``await
  engine.submit(stream)``; a pump task steps the shared pool and resolves each
  client's future on retirement. Transient ``QueueFull`` admission rejections
  are absorbed by a bounded retry-with-backoff before surfacing.
* :func:`serve_resilient` — crash-survivable batch driver with failure
  injection, restore-and-replay, and heartbeat reporting.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import time
import typing
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core import coding, compaction, network, neuron
from repro.core import policy as engine_policy
from repro.serve import slots
from repro.sharding import compat
from repro.sharding import specs as sharding_specs
from repro.train import checkpoint as CKPT
from repro.train import fault_tolerance

NO_SPIKE = int(coding.NO_SPIKE)

#: a named host span on the profiler's clock (``TNNEngine.step``'s phases)
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class TNNServeConfig:
    """Engine knobs: slot count (= batch rows) and neuron-bank backend."""

    n_slots: int = 8
    #: fire_times_bank engine for every ``auto`` layer: scan | closed_form
    #: | event | pallas | pallas_compact | auto, resolved once at
    #: construction (``TNNEngine.engine``). ``auto`` is pallas on a TPU
    #: wherever the kernel can run, closed_form elsewhere. All engines are
    #: bit-exact, so the choice never changes outputs.
    backend: neuron.Backend = "auto"
    #: gamma-cycle pipeline micro-batches per step (DESIGN.md §5.4): 1 =
    #: the barriered schedule; M > 1 streams the slot batch
    #: through the layer stack in M micro-batches
    #: (``network.forward(..., microbatches=M)``) so layer l works micro-batch
    #: t while layer l+1 works micro-batch t-1. Bit-exact for every
    #: backend; a sparse engine's width is measured on the whole slot
    #: batch, so it covers every micro-batch.
    pipeline_microbatches: int = 1
    #: admission control: cap on the pending queue (None = unbounded).
    #: With a cap set, ``submit`` raises
    #: :class:`repro.serve.slots.QueueFull` once the queue holds this many
    #: waiting requests — the burst is rejected explicitly instead of
    #: growing queue latency without bound; rejections are counted in
    #: ``stats()['n_rejected']``.
    max_pending: Optional[int] = None
    # ----------------------------------------- learn while serving (§5.5)
    #: apply per-gamma-cycle layer-local STDP to the live slot batch: a
    #: learning step runs ``network.step`` (forward + minibatch STDP over
    #: the whole batch at the pre-step weights, recurrent carries
    #: threaded) and the engine's weight state advances. Outputs are
    #: computed at the pre-update weights, so a learning step's spike
    #: times are bit-exact with the same step served learning-off.
    learn: bool = False
    #: learning cadence: STDP fires on steps where ``step_id % stdp_every
    #: == 0`` (1 = every gamma cycle, the online rule over live traffic).
    #: Learning steps always run the barriered schedule — minibatch STDP
    #: reduces across the whole batch, a barrier by construction — while
    #: the steps in between keep the configured pipelined schedule.
    stdp_every: int = 1
    #: None (default) selects the deterministic expectation STDP rule —
    #: the replayable choice the crash-recovery contract relies on; an int
    #: seeds the stochastic rule, with the per-step key folded from
    #: ``step_id`` so restore-and-replay still re-draws identically.
    stdp_seed: Optional[int] = None
    # ------------------------------------------------- durability (§5.5)
    #: snapshot directory for ``train/checkpoint.py``'s CheckpointManager;
    #: None disables snapshotting (and makes ``resume=True`` invalid).
    checkpoint_dir: Optional[str] = None
    #: snapshot every N engine steps (0 = never). Snapshots carry the
    #: weights + the persistent step counter + ``n_stdp_updates``; the
    #: atomic-rotation contract means a crash mid-save can never corrupt
    #: the previous snapshot.
    checkpoint_every: int = 0
    #: rotating snapshots kept on disk (CheckpointManager ``keep``).
    checkpoint_keep: int = 3
    #: serialize snapshots off the serve thread (the state is copied to
    #: host numpy synchronously — the step's weights are immutable jax
    #: arrays, so the async writer can never observe a later update).
    checkpoint_async: bool = True
    # ---------------------------------------- graceful degradation (§5.5)
    #: pause learning while the pending queue holds at least this fraction
    #: of ``max_pending`` (requires ``max_pending``); learning resumes the
    #: step pressure clears. Inference never pauses — shedding the STDP
    #: update is the cheap way to serve through a burst.
    learn_pause_queue_frac: Optional[float] = None
    #: pause learning while the previous step's wall-clock exceeded this
    #: many seconds; resumes when a (non-learning) step comes in under it.
    learn_pause_step_s: Optional[float] = None


#: a slot's persistent memory: per-layer recurrent carries, ``None`` entries
#: for feedforward layers (the SlotEntry ``state`` payload — DESIGN.md §5.1)
CarryState = Tuple[Optional[np.ndarray], ...]


@dataclasses.dataclass
class TNNRequest:
    """One client's stream of volleys and its accumulated outputs."""

    req_id: int
    volleys: np.ndarray  # (n_cycles, n_inputs) int32 spike times
    outputs: List[np.ndarray] = dataclasses.field(default_factory=list)
    cursor: int = 0
    #: fraction of this request's lines carrying an in-cycle spike
    #: (measured at submit)
    density: float = 0.0
    #: carry to seed the slot with at admission (stream continuation);
    #: None = fresh all-silent state (``TNNEngine.submit(initial_state=)``)
    initial_state: Optional[CarryState] = None
    #: final per-layer recurrent carries, handed back at retirement (None
    #: until the stream retires, and stays None for feedforward networks);
    #: resubmitting a continuation stream with these as ``initial_state``
    #: continues the stream bit-exactly where it left off
    final_state: Optional[CarryState] = None

    @property
    def n_cycles(self) -> int:
        return int(self.volleys.shape[0])

    @property
    def done(self) -> bool:
        return self.cursor >= self.n_cycles

    def result(self) -> np.ndarray:
        """(n_cycles, C_last, Q_last) int32 post-WTA output spike times."""
        return np.stack(self.outputs, axis=0)


class TNNEngine:
    """Slot-based volley batching over a trained :class:`TNNNetwork`.

    Admission → batch → fire → retire, one gamma cycle per step:

    1. ``admit``: free slots re-fill FIFO from the pending queue.
    2. ``batch``: live slots contribute their next volley; empty rows are
       all-``NO_SPIKE`` (inert).
    3. ``fire``: one jit ``network.forward`` over ``(B, n_inputs)``
       threading the live slots' recurrent carries, on the neuron-bank
       engine resolved once at construction (``engine``).
    4. ``retire``: exhausted requests leave their slots immediately.
    """

    def __init__(
        self,
        params: Sequence[jax.Array],
        net: network.TNNNetwork,
        scfg: Optional[TNNServeConfig] = None,
        mesh: Optional[Mesh] = None,
        resume: bool = False,
    ):
        scfg = scfg or TNNServeConfig()
        # strict construction-time validation: a typo'd backend would
        # otherwise surface only deep inside fire_times_bank at trace time
        valid = typing.get_args(neuron.Backend)
        for name, where in [(scfg.backend, "TNNServeConfig.backend")] + [
                (lc.backend, f"net.layers[{i}].backend")
                for i, lc in enumerate(net.layers)]:
            if name not in valid:
                raise ValueError(
                    f"{where}={name!r}: expected one of {valid}")
        #: optional ("data", "column") device mesh (sharding.specs.tnn_mesh):
        #: weights live column-sharded, each step's slot batch is placed
        #: under the data spec, and the jitted stack traces inside the mesh
        #: scope so the layer constraints bind (DESIGN.md §6.4)
        self.mesh = mesh
        # the one engine decision, inside the mesh scope with every layer's
        # column count: a Pallas engine survives exactly when each layer
        # clears the per-kernel capability check, and an explicit one that
        # cannot run raises here (DESIGN.md §6.4)
        with self._mesh_scope():
            #: the neuron-bank engine every ``auto`` layer runs
            self.engine = engine_policy.resolve_engine(scfg.backend, net.column_counts)
        # pin only the layers that delegated the choice: explicit
        # per-layer backends are respected
        net = network.make_network(
            [
                lc if lc.backend != "auto" else dataclasses.replace(lc, backend=self.engine)
                for lc in net.layers
            ]
        )
        self.net = net
        self.scfg = scfg
        self.params = self._place_params(params)
        if mesh is not None:
            self._batch_sharding = network.data_sharding(net, mesh, scfg.n_slots)
            # recurrent-carry placement: each (B, n_outputs_l) carry batch
            # lands batch-over-data, lines-over-column — the same shards
            # that produced (and will re-consume) those lines, so carry
            # threading moves no data between devices (specs.tnn_carry_pspec)
            self._carry_shardings = tuple(
                NamedSharding(
                    mesh,
                    sharding_specs.tnn_carry_pspec(mesh, scfg.n_slots, lc.n_outputs),
                )
                if lc.recurrent
                else None
                for lc in net.layers
            )
        else:
            self._batch_sharding = None
            self._carry_shardings = (None,) * len(net.layers)
        #: which layers thread a recurrent carry (slot state is live iff any)
        self._recurrent = tuple(lc.recurrent for lc in net.layers)
        self.stateful = any(self._recurrent)
        self.pool: slots.SlotPool[TNNRequest, CarryState] = slots.SlotPool(
            scfg.n_slots,
            on_admit=self._on_admit,
            max_pending=scfg.max_pending,
        )
        if scfg.pipeline_microbatches < 1:
            raise ValueError(
                f"pipeline_microbatches must be >= 1, got {scfg.pipeline_microbatches}"
            )
        # effective micro-batch count — network.microbatch_split is the
        # single encoding, shared with network.forward
        self.n_stages, _ = network.microbatch_split(
            scfg.n_slots, scfg.pipeline_microbatches
        )
        self._fwd = jax.jit(self._forward_fn(net))
        # the other compiled steps, keyed (layer-0 width, learn): a sparse
        # engine's bucketed widths and the STDP step. The bucket ladder
        # under layer 0's rf_total bounds them, so the dict stays small.
        self._variants: Dict[Tuple[Optional[int], bool], object] = {}
        self._t_steps = net.layers[0].t_steps
        # layer-0 receptive-field line ids, host-side: the per-step sparse
        # width is measured on the gathered view the neuron banks will see
        self._rf0 = np.asarray(net.layers[0].rf_index())
        self._next_id = 0
        # timestamp-only entries (item=None) — see step()
        self._retired: List[slots.SlotEntry] = []
        self.n_steps = 0
        self.n_volleys = 0
        self._run_s = 0.0
        #: steps whose batch the host measured (explicit sparse engines)
        self._steps_measured = 0
        # ---------------------------------- learning + durability (§5.5)
        if scfg.stdp_every < 1:
            raise ValueError(f"stdp_every must be >= 1, got "
                             f"{scfg.stdp_every}")
        if scfg.checkpoint_every < 0:
            raise ValueError(f"checkpoint_every must be >= 0, got "
                             f"{scfg.checkpoint_every}")
        if scfg.checkpoint_every and scfg.checkpoint_dir is None:
            raise ValueError("checkpoint_every > 0 needs checkpoint_dir")
        if scfg.learn_pause_queue_frac is not None:
            if scfg.max_pending is None:
                raise ValueError("learn_pause_queue_frac measures "
                                 "max_pending occupancy — set max_pending")
            if scfg.learn_pause_queue_frac <= 0.0:
                raise ValueError("learn_pause_queue_frac must be > 0")
        self._stdp_base_key = (
            jax.random.PRNGKey(scfg.stdp_seed)
            if scfg.stdp_seed is not None else None)
        #: persistent engine-step counter: unlike ``n_steps`` it survives
        #: ``reset_stats`` and restores with snapshots — the STDP cadence,
        #: the snapshot schedule, and the stochastic-rule keys all key off
        #: it, so a restored engine replays the exact same decisions.
        self.step_id = 0
        self.n_stdp_updates = 0
        self.n_snapshots = 0
        self.n_restores = 0
        self.learning_paused = False
        self.n_learn_pauses = 0
        self._last_step_s = 0.0
        self._ckpt: Optional[CKPT.CheckpointManager] = None
        if scfg.checkpoint_dir is not None and scfg.checkpoint_every > 0:
            self._ckpt = CKPT.CheckpointManager(
                scfg.checkpoint_dir, keep=scfg.checkpoint_keep,
                every=scfg.checkpoint_every,
                async_save=scfg.checkpoint_async)
        if resume:
            if self._ckpt is None:
                raise ValueError("resume=True needs checkpoint_dir and "
                                 "checkpoint_every > 0")
            if CKPT.latest_step(self._ckpt.dir) is not None:
                self.restore()
        # host-side reference weights for the per-layer drift norms (and
        # the no-snapshot restore fallback): the engine's weights as of
        # construction — post-resume, so drift measures learning since
        # THIS service instance came up
        self._params_host0 = tuple(np.asarray(p) for p in self.params)

    def _forward_fn(self, net: network.TNNNetwork, learn: bool = False):
        """Step function over a (possibly engine-pinned) network.

        Inference (``learn=False``): ``network.forward`` with the engine's
        micro-batch count — the barriered schedule at M=1, the §5.4
        pipelined schedule above it, bit-exact either way, so every jit
        variant (:meth:`_fwd_for`) shares it. Signature ``(params, volleys,
        carry) -> (out, carry_out)``; the carry tuple's ``None`` entries
        (feedforward layers, or every layer in a stateless network) vanish
        from the jit pytree, so a feedforward engine compiles the exact
        same step it always did.

        Learning (``learn=True``): ``network.step`` — forward + layer-local
        minibatch STDP with the carry threaded, weights in / weights out as
        explicit jit state (never closed over, so a weight update is a new
        argument, not a recompile). Signature ``(params, volleys, carry,
        key) -> (out, carry_out, new_params)``; ``key=None`` (an empty
        pytree) selects the deterministic expectation rule. Learning steps
        are whole-batch barriers (minibatch STDP reduces across the batch),
        so the micro-batch count does not apply — outputs stay bit-exact
        with the pipelined inference schedule regardless.
        """
        m = self.n_stages

        if learn:
            def fn(p, v, c, k):
                res = network.step(p, v, net, key=k, carry=c)
                return res.out, res.carry, res.params

            return fn

        def fn(p, v, c):
            res = network.forward(p, v, net, microbatches=m, carry=c)
            return res.out, res.carry

        return fn

    def _place_params(self, params: Sequence) -> Tuple[jax.Array, ...]:
        """Weight stacks -> device(s): column-sharded under the engine's
        mesh (``network.param_shardings``), plain device arrays otherwise.
        Shared by construction and the :meth:`restore` rollback, so a
        restored engine's weights land exactly where the originals did."""
        if self.mesh is not None:
            return jax.device_put(
                tuple(jnp.asarray(p) for p in params),
                network.param_shardings(self.net, self.mesh),
            )
        return tuple(jnp.asarray(p) for p in params)

    def _stdp_key(self) -> Optional[jax.Array]:
        """Per-step STDP key: ``None`` (deterministic expectation rule)
        unless ``stdp_seed`` was set, in which case the base key folded
        with the persistent ``step_id`` — a restored engine replaying step
        s re-draws the exact same randomness it drew the first time."""
        if self._stdp_base_key is None:
            return None
        return jax.random.fold_in(self._stdp_base_key, self.step_id)

    def _learn_gate(self) -> bool:
        """Should THIS step apply STDP? The §5.5 graceful-degradation
        rule: learning pauses (inference never does) while admission
        pressure — pending-queue occupancy or the previous step's
        wall-clock — sits above the configured thresholds, and resumes
        the step pressure clears. Pause transitions are counted
        (``stats()['n_learn_pauses']``)."""
        scfg = self.scfg
        if not scfg.learn:
            return False
        pressured = (
            scfg.learn_pause_queue_frac is not None
            and self.pool.pending_occupancy >= scfg.learn_pause_queue_frac
        ) or (
            scfg.learn_pause_step_s is not None
            and self._last_step_s > scfg.learn_pause_step_s
        )
        if pressured:
            if not self.learning_paused:
                self.learning_paused = True
                self.n_learn_pauses += 1
            return False
        self.learning_paused = False
        return self.step_id % scfg.stdp_every == 0

    def _snapshot_state(self) -> Dict[str, object]:
        """The durable state a snapshot carries: the weight stacks plus
        the persistent counters (``step_id``, ``n_stdp_updates``) — enough
        to make a restored engine's cadence/key/snapshot decisions
        identical to the original run's."""
        return {
            "params": tuple(self.params),
            "counters": np.asarray(
                [self.step_id, self.n_stdp_updates], np.int32),
        }

    def _maybe_snapshot(self) -> None:
        """Hand the step's state to the CheckpointManager on the
        ``checkpoint_every`` cadence. With ``checkpoint_async`` the
        manager copies to host numpy synchronously and serializes on its
        own thread — the weights are immutable jax arrays, so a later
        STDP update can never leak into an in-flight save."""
        if self._ckpt is None or not self._ckpt.due(self.step_id):
            return
        with _span("tnn.snapshot"):
            if self._ckpt.maybe_save(self.step_id, self._snapshot_state()):
                self.n_snapshots += 1

    def checkpoint_wait(self) -> None:
        """Block until any in-flight async snapshot has published."""
        if self._ckpt is not None:
            self._ckpt.wait()

    def restore(self) -> int:
        """Roll the engine back to the latest snapshot — or, with none on
        disk yet, to its construction-time weights (construction is the
        implicit step-0 commit point). Restores the weights and the
        persistent counters, then drops every live/pending stream
        (``pool.clear()``): their partial progress was computed at
        weights that no longer exist, so the §5.5 contract is
        restore-and-replay — the driver (:func:`serve_resilient`)
        resubmits every stream not committed by the restored snapshot,
        from its beginning. Returns the restored step id.
        """
        if self._ckpt is None:
            raise ValueError(
                "restore() needs checkpoint_dir and checkpoint_every > 0")
        self.checkpoint_wait()
        step = CKPT.latest_step(self._ckpt.dir)
        if step is None:
            self.params = self._place_params(self._params_host0)
            self.step_id = 0
            self.n_stdp_updates = 0
        else:
            template = {
                "params": tuple(self.params),
                "counters": np.zeros(2, np.int32),
            }
            state = CKPT.restore_checkpoint(self._ckpt.dir, template, step)
            self.params = tuple(state["params"])
            counters = np.asarray(state["counters"])
            self.step_id = int(counters[0])
            self.n_stdp_updates = int(counters[1])
        self.pool.clear()
        self.learning_paused = False
        self._last_step_s = 0.0
        self.n_restores += 1
        return self.step_id

    def _on_admit(self, idx: int, entry: slots.SlotEntry) -> None:
        """Pool lifecycle hook: initialise the slot's per-layer recurrent
        state all-silent (NO_SPIKE) — cycle 0 of a fresh stream is exactly
        feedforward. A submitted request carrying an ``initial_state``
        resumes from that carry instead (stream continuation)."""
        del idx
        req = entry.item
        if req is not None and req.initial_state is not None:
            # continuation: the request was seeded with a prior carry
            entry.state = req.initial_state
            return
        if self.stateful:
            entry.state = tuple(
                np.full((lc.n_outputs,), NO_SPIKE, np.int32) if lc.recurrent else None
                for lc in self.net.layers
            )

    def reset_stats(self) -> None:
        """Zero the throughput/latency accounting (e.g. after jit warmup);
        pending/live requests and the compiled step are untouched."""
        self._retired.clear()
        self.n_steps = 0
        self.n_volleys = 0
        self._run_s = 0.0
        self._steps_measured = 0
        self.pool.n_retired = 0
        self.pool.n_rejected = 0
        self.pool.n_submitted = self.pool.n_live + self.pool.n_pending

    def submit(
        self,
        volleys: np.ndarray,
        initial_state: Optional[CarryState] = None,
    ) -> TNNRequest:
        """Enqueue one request: ``(n_cycles, n_inputs)`` int32 spike times
        (a single ``(n_inputs,)`` volley is promoted to one cycle).

        ``initial_state`` seeds the slot's recurrent carry at admission —
        pass a retired request's ``final_state`` to continue its stream
        bit-exactly. Raises :class:`repro.serve.slots.QueueFull` when the
        engine runs with ``max_pending`` and the queue is full (counted in
        ``stats()['n_rejected']``)."""
        volleys = np.asarray(volleys, np.int32)
        if volleys.ndim == 1:
            volleys = volleys[None, :]
        if volleys.ndim != 2 or volleys.shape[1] != self.net.n_inputs:
            raise ValueError(
                f"expected (n_cycles, {self.net.n_inputs}) volleys, got {volleys.shape}"
            )
        if volleys.shape[0] == 0:
            raise ValueError("empty volley stream")
        if (volleys < 0).any():
            # negative times would silently count as "active" in the density
            # measurement and violate the event engine's breakpoint-sort
            # contract (spike times are ticks in [0, T) or NO_SPIKE)
            raise ValueError(
                "volleys must be non-negative spike times "
                f"(NO_SPIKE={NO_SPIKE} for silent lines); got min "
                f"{int(volleys.min())}"
            )
        if initial_state is not None:
            if not self.stateful:
                raise ValueError("initial_state given for a feedforward network")
            if len(initial_state) != len(self.net.layers):
                raise ValueError(
                    f"initial_state has {len(initial_state)} entries for "
                    f"{len(self.net.layers)} layers"
                )
            initial_state = tuple(
                None if c is None else np.asarray(c, np.int32).reshape(lc.n_outputs)
                for c, lc in zip(initial_state, self.net.layers)
            )
        density = float(np.mean(volleys < self._t_steps))
        req = TNNRequest(
            req_id=self._next_id,
            volleys=volleys,
            density=density,
            initial_state=initial_state,
        )
        # pool.submit may reject (QueueFull); only a queued request
        # consumes a request id
        self.pool.submit(req)
        self._next_id += 1
        return req

    def _mesh_scope(self):
        """Ambient-mesh context for jit trace/execute; no-op without one."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return compat.set_mesh(self.mesh)

    def _place(self, batch: np.ndarray) -> jax.Array:
        """Host batch -> device(s): under a mesh the (B, n_inputs) block is
        placed batch-over-``data`` before the jit boundary (a sparse
        engine's width measurement stays host-side, on the numpy batch)."""
        if self._batch_sharding is None:
            return jnp.asarray(batch)
        return jax.device_put(batch, self._batch_sharding)

    def _place_carry(self, carry_np: CarryState):
        """Per-layer host carry batches -> device(s), under the §6.5 carry
        rule when a mesh is active (``None`` entries pass through)."""
        return tuple(
            None
            if c is None
            else (jnp.asarray(c) if sh is None else jax.device_put(c, sh))
            for c, sh in zip(carry_np, self._carry_shardings)
        )

    def _layer0_active(self, batch: np.ndarray) -> int:
        """Max active-line count over the batch's layer-0 receptive
        fields (exact measurement, so no active line can drop; the step
        buckets it onto the compaction ladder)."""
        active = batch[:, self._rf0] < self._t_steps  # (B, C, rf)
        return int(active.sum(axis=-1).max()) if active.size else 0

    def _fwd_for(self, first_width: Optional[int] = None, learn: bool = False):
        """The jitted step for a layer-0 compaction width and a learn flag.

        ``(None, False)`` is the compiled ``self._fwd``. Any other key
        lazily compiles a variant: sparse layers get static compaction
        widths (``network.sparse_widths`` seeded with the measured and
        bucketed ``first_width``), so the jitted stack runs the compacted
        solve; ``learn=True`` selects the STDP step (``_forward_fn(...,
        learn)``: forward + weight update, weights as explicit jit state,
        so a weight update never forces a compile).
        """
        if first_width is None and not learn:
            return self._fwd
        key = (first_width, learn)
        fwd = self._variants.get(key)
        if fwd is not None:
            return fwd
        net = self.net
        if first_width is not None:
            sparse = engine_policy.SPARSE_ENGINES
            widths = network.sparse_widths(net, first_width)
            net = network.make_network(
                [
                    dataclasses.replace(lc, n_active_max=w) if lc.backend in sparse else lc
                    for lc, w in zip(net.layers, widths)
                ]
            )
        fwd = self._variants[key] = jax.jit(self._forward_fn(net, learn))
        return fwd

    def step(self) -> List[TNNRequest]:
        """One gamma cycle for every live slot; returns requests retired
        this step (in ascending slot order).

        The step and each of its phases run inside a named profiler span
        (``tnn.step``; ``tnn.admit``, ``tnn.batch``, ``tnn.measure``,
        ``tnn.resolve``, ``tnn.h2d``, ``tnn.dispatch``, ``tnn.fetch``,
        ``tnn.retire``, and ``tnn.snapshot`` on the steps that snapshot),
        so a trace puts the host's work on the device trace's clock. With
        no profiler running a span costs about a microsecond."""
        t0 = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("tnn.step",
                                              step_num=self.step_id):
            with _span("tnn.admit"):
                self.pool.admit()
                live = list(self.pool.live())
            if not live:
                return []
            with _span("tnn.batch"):
                batch = np.full((self.scfg.n_slots, self.net.n_inputs),
                                NO_SPIKE, np.int32)
                # per-layer recurrent carry batches from the live slots'
                # state; free rows stay all-NO_SPIKE (silent carries are
                # inert, like their input rows), so the batch stays
                # shape-static
                carry_np: CarryState = tuple(
                    np.full((self.scfg.n_slots, lc.n_outputs), NO_SPIKE,
                            np.int32)
                    if lc.recurrent
                    else None
                    for lc in self.net.layers
                )
                for idx, entry in live:
                    req = entry.item
                    batch[idx] = req.volleys[req.cursor]
                    if self.stateful:
                        for c, s in zip(carry_np, entry.state):
                            if c is not None:
                                c[idx] = s
            with self._mesh_scope():
                with _span("tnn.measure"):
                    # only an explicit sparse engine reads the batch: its
                    # static compaction width is bucketed from this
                    # batch's own layer-0 receptive fields (exact-covering,
                    # so no active line drops)
                    width = None
                    if self.engine in engine_policy.SPARSE_ENGINES:
                        width = compaction.bucket_width(self._layer0_active(batch))
                        self._steps_measured += 1
                with _span("tnn.resolve"):
                    learn = self._learn_gate()
                with _span("tnn.h2d"):
                    batch_dev = self._place(batch)
                    carry_in = self._place_carry(carry_np)
                with _span("tnn.dispatch"):
                    if learn:
                        # STDP step: outputs at the pre-update weights
                        # (bit-exact with the inference path), new weights
                        # advance the engine's explicit state — no
                        # recompile, and under a mesh the update stays
                        # column-sharded (layer_step pins it via
                        # specs.tnn_param_axes)
                        out_dev, carry_dev, new_params = self._fwd_for(
                            width, learn=True
                        )(self.params, batch_dev, carry_in, self._stdp_key())
                        self.params = new_params
                        self.n_stdp_updates += 1
                    else:
                        out_dev, carry_dev = self._fwd_for(width)(
                            self.params, batch_dev, carry_in
                        )
                with _span("tnn.fetch"):
                    out = np.asarray(out_dev)
                    carry_out = tuple(
                        None if c is None else np.asarray(c)
                        for c in carry_dev
                    )
            retired: List[TNNRequest] = []
            with _span("tnn.retire"):
                for idx, entry in live:
                    req = entry.item
                    # copy: out[idx] is a view that would pin the whole
                    # (B, C, Q) batch array for the life of the request
                    req.outputs.append(out[idx].copy())
                    req.cursor += 1
                    if self.stateful:
                        # scatter this row's new carry back into the
                        # slot's state
                        entry.state = tuple(
                            None if c is None else c[idx].copy()
                            for c in carry_out
                        )
                    if req.done:
                        done_entry = self.pool.retire(idx)
                        # the final carry leaves the pool on the entry;
                        # hand it to the request so the client can
                        # continue the stream later
                        req.final_state = done_entry.state
                        # keep only the timestamps for the latency summary
                        # — holding the request (volleys + outputs +
                        # state) would grow without bound in a long-lived
                        # service
                        self._retired.append(
                            dataclasses.replace(done_entry, item=None,
                                                state=None)
                        )
                        retired.append(req)
            self.n_steps += 1
            self.n_volleys += len(live)
            # persistent counter + snapshot cadence: step_id advances AFTER
            # the step's retirements, so a snapshot at step s commits every
            # stream retired at-or-before s (the serve_resilient commit
            # rule); advancing first also keeps maybe_save from firing at
            # step 0
            self.step_id += 1
            self._maybe_snapshot()
        dt = time.perf_counter() - t0
        self._last_step_s = dt
        self._run_s += dt
        return retired

    def run(self) -> List[TNNRequest]:
        """Drain pending + live work; returns requests in completion order."""
        finished: List[TNNRequest] = []
        while self.pool.has_work:
            finished.extend(self.step())
        return finished

    def serve(self, streams: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Synchronous front door: results in submission order."""
        reqs = [self.submit(s) for s in streams]
        self.run()
        return [r.result() for r in reqs]

    def stats(self) -> Dict[str, float]:
        """Throughput + occupancy + per-request latency summary."""
        out = {
            "n_steps": float(self.n_steps),
            "n_volleys": float(self.n_volleys),
            "n_retired": float(self.pool.n_retired),
            "n_rejected": float(self.pool.n_rejected),
            "run_s": self._run_s,
        }
        if self._run_s > 0.0:
            out["volleys_per_s"] = self.n_volleys / self._run_s
        if self.n_steps > 0:
            denom = self.n_steps * self.scfg.n_slots
            out["slot_occupancy"] = self.n_volleys / denom
        # steps whose batch the host measured (explicit sparse engines)
        out["steps_measured"] = float(self._steps_measured)
        out["pipeline_microbatches"] = float(self.n_stages)
        # compiled steps besides the default self._fwd
        out["jit_variants"] = float(len(self._variants))
        # §5.5 learning + durability counters (step_id is the persistent
        # counter snapshots carry; n_steps above is the resettable stat)
        out["step_id"] = float(self.step_id)
        out["n_stdp_updates"] = float(self.n_stdp_updates)
        out["n_snapshots"] = float(self.n_snapshots)
        out["n_restores"] = float(self.n_restores)
        out["learning_paused"] = float(self.learning_paused)
        out["n_learn_pauses"] = float(self.n_learn_pauses)
        if self.scfg.learn:
            # per-layer L2 drift vs the weights this instance came up with
            # (post-resume) — how far live traffic has moved each stack
            for i, (p, p0) in enumerate(zip(self.params, self._params_host0)):
                out[f"weight_drift_l{i}"] = float(
                    np.linalg.norm(
                        np.asarray(p, np.float64) - np.asarray(p0, np.float64)
                    )
                )
        out.update(slots.latency_summary(self._retired))
        return out


class AsyncTNNEngine:
    """``asyncio`` front door over a shared :class:`TNNEngine`.

    Clients ``await submit(stream)`` concurrently; a single pump task steps
    the engine while work remains, resolving each request's future when it
    retires. The step itself is synchronous compute (one jit call), so the
    pump yields control between steps — admission stays continuous under
    concurrent submission bursts.

    Admission rejections (``max_pending`` hit — :class:`slots.QueueFull`)
    are absorbed by a bounded retry: the submitter backs off
    ``submit_retry_delay_s`` (with the pump kept running, so each backoff
    gives the engine a chance to drain the queue) up to ``submit_retries``
    times before the exception surfaces to the caller. A transient burst
    rides through; sustained overload still fails fast.
    """

    def __init__(
        self,
        engine: TNNEngine,
        *,
        submit_retries: int = 3,
        submit_retry_delay_s: float = 0.02,
    ):
        if submit_retries < 0:
            raise ValueError(f"submit_retries must be >= 0, got {submit_retries}")
        if submit_retry_delay_s < 0:
            raise ValueError(
                f"submit_retry_delay_s must be >= 0, got {submit_retry_delay_s}"
            )
        self.engine = engine
        self.submit_retries = submit_retries
        self.submit_retry_delay_s = submit_retry_delay_s
        self._futures: Dict[int, asyncio.Future] = {}
        self._pump_task: Optional[asyncio.Task] = None

    async def submit(self, volleys: np.ndarray) -> np.ndarray:
        """Submit one stream; resolves to its (n_cycles, C, Q) output.

        A full pending queue is retried ``submit_retries`` times with
        ``submit_retry_delay_s`` backoff; :class:`slots.QueueFull`
        propagates once the budget is spent (each rejected attempt still
        counts in ``stats()['n_rejected']``)."""
        for attempt in range(self.submit_retries + 1):
            try:
                req = self.engine.submit(volleys)
                break
            except slots.QueueFull:
                if attempt == self.submit_retries:
                    raise
                # keep the pump stepping so the queue can actually drain
                # while this submitter backs off
                self._ensure_pump()
                await asyncio.sleep(self.submit_retry_delay_s)
        fut = asyncio.get_running_loop().create_future()
        self._futures[req.req_id] = fut
        self._ensure_pump()
        return await fut

    def _ensure_pump(self) -> None:
        if self._pump_task is None or self._pump_task.done():
            self._pump_task = asyncio.get_running_loop().create_task(self._pump())

    async def _pump(self) -> None:
        try:
            while self.engine.pool.has_work:
                for req in self.engine.step():
                    fut = self._futures.pop(req.req_id, None)
                    if fut is not None and not fut.done():
                        fut.set_result(req.result())
                # yield so freshly woken clients can enqueue before next admit
                await asyncio.sleep(0)
        except Exception as exc:
            # a dead pump must not strand awaiting clients: fail them all.
            # No re-raise — every request holds a future, so the error is
            # fully delivered; re-raising would only produce an unretrieved
            # task exception at GC (the pump task is never awaited).
            for fut in self._futures.values():
                if not fut.done():
                    fut.set_exception(exc)
            self._futures.clear()


def serve_resilient(
    engine: TNNEngine,
    streams: Sequence[np.ndarray],
    *,
    failure_injector: Optional[callable] = None,
    max_restarts: int = 3,
    monitor: Optional[fault_tolerance.HeartbeatMonitor] = None,
) -> Tuple[List[np.ndarray], dict]:
    """Crash-survivable batch serving: the ``run_resilient`` idiom for the
    serve path (DESIGN.md §5.5).

    Feeds ``streams`` through the engine (incrementally, so a bounded
    pending queue never rejects the batch), stepping until everything
    retires. ``failure_injector(step_id)`` may raise
    :class:`~repro.train.fault_tolerance.WorkerFailure` to simulate a node
    loss mid-serve; on failure the driver rolls the engine back to its
    latest snapshot (:meth:`TNNEngine.restore` — weights + persistent
    counters, pool cleared) and replays every stream **not committed** by
    that snapshot from its beginning. A snapshot at step ``s`` commits
    exactly the streams retired at-or-before ``s`` (``step_id`` advances
    after a step's retirements, before its snapshot), so the contract is
    exactly-once per retired stream: committed results are never
    recomputed, uncommitted streams are resubmitted whole. With learning
    off, replayed outputs are bit-exact with the uninterrupted run (slot
    outputs are batch-composition-invariant); with learning on, the
    deterministic STDP rule + restored counters make the replayed weight
    trajectory identical from the snapshot forward.

    Each step beats ``monitor`` (host 0) with its wall-clock when one is
    given. Returns ``(results, report)``: results in submission order,
    report with ``restarts``, ``failed_hosts``, ``restored_steps``, and
    ``resubmitted`` (one list of stream indices per restore). Re-raises
    the failure once ``max_restarts`` is exhausted.
    """
    n = len(streams)
    results: List[Optional[np.ndarray]] = [None] * n
    retired_step: List[Optional[int]] = [None] * n
    report = {
        "restarts": 0,
        "failed_hosts": [],
        "restored_steps": [],
        "resubmitted": [],
    }
    todo = collections.deque(range(n))
    inflight: Dict[int, int] = {}
    restarts = 0

    def _feed() -> None:
        # fill the queue as far as admission control allows; the rest
        # waits in `todo` for freed capacity
        while todo:
            try:
                req = engine.submit(streams[todo[0]])
            except slots.QueueFull:
                break
            inflight[req.req_id] = todo.popleft()

    while True:
        try:
            _feed()
            while inflight or todo or engine.pool.has_work:
                t0 = time.perf_counter()
                if failure_injector is not None:
                    failure_injector(engine.step_id)
                for req in engine.step():
                    i = inflight.pop(req.req_id, None)
                    if i is None:
                        continue  # not ours (caller pre-submitted work)
                    results[i] = req.result()
                    retired_step[i] = engine.step_id
                if monitor is not None:
                    monitor.beat(0, time.perf_counter() - t0)
                _feed()
            engine.checkpoint_wait()
            return results, report
        except fault_tolerance.WorkerFailure as f:
            restarts += 1
            report["restarts"] = restarts
            report["failed_hosts"].append(f.host_id)
            if restarts > max_restarts:
                raise
            s = engine.restore()
            report["restored_steps"].append(s)
            # roll back everything the restored snapshot didn't commit:
            # results recorded after step s are stale (computed at weights
            # that no longer exist) — drop them and replay those streams
            inflight.clear()
            replay = [
                i
                for i in range(n)
                if retired_step[i] is None or retired_step[i] > s
            ]
            for i in replay:
                results[i] = None
                retired_step[i] = None
            todo = collections.deque(replay)
            report["resubmitted"].append(replay)


def reference_outputs(
    params: Sequence[jax.Array],
    net: network.TNNNetwork,
    stream: np.ndarray,
) -> np.ndarray:
    """Unbatched oracle: each volley through ``network.forward`` alone,
    threading the stream's own recurrent carry across cycles (silent for
    cycle 0 — a fresh stream).

    The bit-exactness target for the slot engine (and the honest
    per-request baseline for the serving benchmark).
    """
    outs: List[np.ndarray] = []
    carry = None
    for volley in np.asarray(stream, np.int32):
        res = network.forward(tuple(params), jnp.asarray(volley), net, carry=carry)
        carry = res.carry
        outs.append(np.asarray(res.out))
    return np.stack(outs, axis=0)
