"""Multi-layer TNN: a stack of TNNLayers over a stream of volleys
(DESIGN.md §6.3).

Feedforward TNNs (Smith [13]; Vellaisamy & Shen's SPU design framework)
compose columns layer by layer: each layer's post-WTA output spikes — at
most one line hot per column, carrying the winner's fire *time* — form the
input volley of the next layer. Flattened, layer l emits
``n_columns * n_neurons`` lines, which must equal layer l+1's ``n_inputs``
(checked at construction).

Learning is layer-local (greedy): STDP in every layer uses only that
layer's own input slice and WTA outcome, so one forward sweep trains all
layers simultaneously — no backward pass exists in a TNN. All functions
are jit/scan friendly; weights are a tuple of (C, Q, rf_total) arrays.

Stateful streams: a recurrent layer (``TNNLayer.recurrent``) also sees its
own previous-cycle output volley, so the network-level entry point is
:func:`forward` — one call per gamma cycle threading an explicit per-layer
``carry`` (previous outputs in, this cycle's outputs out; DESIGN.md
§6.3).
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.core import coding
from repro.core import layer as layer_mod
from repro.core import neuron
from repro.sharding import specs as sharding_specs


@dataclasses.dataclass(frozen=True)
class TNNNetwork:
    layers: Tuple[layer_mod.TNNLayer, ...]

    def __post_init__(self):
        if not self.layers:
            raise ValueError("network needs at least one layer")
        for i in range(1, len(self.layers)):
            prev, cur = self.layers[i - 1], self.layers[i]
            if prev.n_outputs != cur.n_inputs:
                raise ValueError(
                    f"layer {i - 1} emits {prev.n_outputs} lines but layer "
                    f"{i} consumes {cur.n_inputs}")

    @property
    def n_inputs(self) -> int:
        return self.layers[0].n_inputs

    @property
    def n_outputs(self) -> int:
        return self.layers[-1].n_outputs

    @property
    def column_counts(self) -> Tuple[int, ...]:
        """Per-layer column counts — the shape input to the Pallas mesh
        capability check; callers resolving one engine for the whole
        stack (the serve engine) pass this to
        :func:`repro.core.policy.resolve_engine`, so ``auto`` leaves the
        Pallas engines exactly when some layer cannot tile the mesh."""
        return tuple(lc.n_columns for lc in self.layers)


def make_network(layers: Sequence[layer_mod.TNNLayer]) -> TNNNetwork:
    return TNNNetwork(layers=tuple(layers))


def param_shardings(cfg: TNNNetwork, mesh: Mesh
                    ) -> Tuple[NamedSharding, ...]:
    """Per-layer NamedShardings for the (C_l, Q_l, rf_l) weight stacks:
    columns over the ``column`` axis, replication fallback when C_l does
    not divide it (DESIGN.md §6.4)."""
    return tuple(
        NamedSharding(mesh, sharding_specs.tnn_param_pspec(mesh,
                                                           lc.n_columns))
        for lc in cfg.layers)


def data_sharding(cfg: TNNNetwork, mesh: Mesh, batch: int) -> NamedSharding:
    """Sharding for a (B, n_inputs) input volley batch: B over ``data``."""
    del cfg  # shape-independent; kept for signature symmetry
    return NamedSharding(mesh, sharding_specs.tnn_batch_pspec(mesh, batch))


def init_network(key: jax.Array, cfg: TNNNetwork,
                 mesh: Optional[Mesh] = None) -> Tuple[jax.Array, ...]:
    """Random per-layer weights; with ``mesh`` each layer's (C, Q, rf)
    stack is placed under its :func:`param_shardings` layout (init itself
    stays replicated math — bit-identical to the unsharded init)."""
    keys = jax.random.split(key, len(cfg.layers))
    params = tuple(layer_mod.init_layer(k, lc)
                   for k, lc in zip(keys, cfg.layers))
    if mesh is not None:
        params = jax.device_put(params, param_shardings(cfg, mesh))
    return params


class ForwardResult(NamedTuple):
    """Everything one gamma cycle produces (:func:`forward`).

    ``out``: (B, C_last, Q_last) int32 post-WTA spike times of the last
    layer. ``winners``: per-layer (B, C_l) winner indices (the network's
    spike-train activation trace). ``carry``: per-layer next-cycle carry —
    layer l's flattened output volley (B, n_outputs_l) for recurrent
    layers, ``None`` for feedforward ones; feed it back as the next call's
    ``carry`` to advance a stream. ``densities``: per-layer measured input
    densities when requested (``with_densities=True``), else ``None``. A
    1-D single volley drops the batch dim from every array field.
    """

    out: jax.Array
    winners: Tuple[jax.Array, ...]
    carry: Tuple[Optional[jax.Array], ...]
    densities: Optional[List[Optional[float]]]


def init_carry(cfg: TNNNetwork, batch: int
               ) -> Tuple[Optional[jax.Array], ...]:
    """Per-layer all-silent carry for the first gamma cycle of a stream:
    (batch, n_outputs_l) all-``NO_SPIKE`` for recurrent layers, ``None``
    for feedforward ones. ``forward(..., carry=None)`` feeds exactly this,
    so a recurrent stack's cycle 0 is bit-exact feedforward."""
    return tuple(layer_mod.carry_init(lc, batch) if lc.recurrent else None
                 for lc in cfg.layers)


def forward(params: Sequence[jax.Array], volleys: jax.Array,
            cfg: TNNNetwork, *, microbatches: int = 1,
            with_densities: bool = False,
            carry: Optional[Sequence[Optional[jax.Array]]] = None
            ) -> ForwardResult:
    """One gamma cycle through the whole stack — THE forward entry point.

    Unifies the historical variant trio: ``microbatches > 1`` runs the
    §5.4 software-pipelined schedule (bit-exact vs the barriered one for
    every backend and any M), ``with_densities=True`` records each layer's
    measured input density on the same activations (host-side diagnostic;
    barriered only), and ``carry`` threads recurrent state — per-layer
    previous-cycle output volleys, ``None`` entries (or ``carry=None``)
    meaning the all-silent first cycle of a stream
    (:func:`init_carry`).

    Pipelined carry scheduling: layer l consumes micro-batch j at tick
    l + j, so each recurrent layer's carry slab is fed to the scan shifted
    by l ticks (silent blocks elsewhere) and its per-tick outputs are
    collected back into the next cycle's carry — state threads through the
    pipeline with no extra barrier.

    Args:
      params:  per-layer weights, layer l shaped (C_l, Q_l, rf_total_l).
      volleys: (B, n_inputs) int32 input spike volleys — or (n_inputs,)
        for a single volley (batch dim dropped from every result field).
      microbatches: pipeline micro-batches M (clamped to [1, B]).
      with_densities: also report per-layer measured input densities
        (requires ``microbatches == 1``).
      carry: per-layer carry-in, layer l (B, n_outputs_l) int32 for
        recurrent layers (1-D for a single volley), ``None`` for
        feedforward ones; ``carry=None`` = all-silent.

    Returns:
      :class:`ForwardResult` — ``result.carry`` is the carry-in for the
      stream's next gamma cycle.
    """
    n_layers = len(cfg.layers)
    if carry is None:
        carry_in: Tuple[Optional[jax.Array], ...] = (None,) * n_layers
    else:
        if len(carry) != n_layers:
            raise ValueError(f"carry has {len(carry)} entries for "
                             f"{n_layers} layers")
        carry_in = tuple(carry)
    single = volleys.ndim == 1
    x = volleys[None, :] if single else volleys
    x = x.astype(jnp.int32)
    if single:
        carry_in = tuple(c[None, :] if c is not None and c.ndim == 1 else c
                         for c in carry_in)
    b = x.shape[0]
    m, rows = microbatch_split(b, microbatches)
    if with_densities and m > 1:
        raise ValueError("with_densities requires microbatches == 1 "
                         "(density measurement is a host-side whole-batch "
                         "diagnostic)")
    if m > 1:
        res = _forward_pipelined(params, x, cfg, carry_in, m, rows)
    else:
        res = _forward_barriered(params, x, cfg, carry_in, with_densities)
    if single:
        res = ForwardResult(
            out=res.out[0],
            winners=tuple(w[0] for w in res.winners),
            carry=tuple(c if c is None else c[0] for c in res.carry),
            densities=res.densities)
    return res


def _forward_barriered(params, x, cfg, carry_in, with_densities
                       ) -> ForwardResult:
    """Whole-batch barrier at every layer (the M=1 schedule)."""
    winners_all, carry_out = [], []
    densities: Optional[list] = [] if with_densities else None
    out = None
    for w, lc, c in zip(params, cfg.layers, carry_in):
        if densities is not None:
            densities.append(layer_mod.layer_input_density(x, lc, c))
        out, winners = layer_mod.layer_forward(w, x, lc, c)
        winners_all.append(winners)
        x = out.reshape(out.shape[0], lc.n_outputs)   # spike times forward
        carry_out.append(x if lc.recurrent else None)
    return ForwardResult(out, tuple(winners_all), tuple(carry_out),
                         densities)


def microbatch_split(batch: int, microbatches: int) -> Tuple[int, int]:
    """(effective M, rows per micro-batch) for a pipelined split (§5.4).

    Clamps ``microbatches`` to [1, batch], ceil-splits the rows, then
    recomputes the effective count (a ragged batch can need fewer
    micro-batches than requested). The single encoding of the split —
    :func:`forward` schedules with it and the serve engine reports it
    (``TNNEngine.stats()['pipeline_microbatches']``).
    """
    if batch <= 0:
        return 0, 0
    m = max(1, min(int(microbatches), batch))
    rows = -(-batch // m)
    return -(-batch // rows), rows


def _forward_pipelined(params, x, cfg, carry_in, m, rows) -> ForwardResult:
    """One gamma cycle through the stack, software-pipelined (§5.4).

    Learning and inference in a TNN are layer-local, so layer l never
    needs anything from layer l+1 — the barriered schedule's whole-batch
    barrier at every layer is a scheduling choice, not a data dependency.
    This schedule splits the batch into M micro-batches and streams them:
    at pipeline tick t, layer l computes micro-batch t - l, so all L
    layers run concurrently on distinct micro-batches (``lax.scan`` over
    a shifted stage buffer). Warmup/drain ticks feed all-``NO_SPIKE``
    stage buffers (:func:`repro.core.layer.stage_init`) — silent volleys
    fire nothing, so the padding is inert and the valid rows are sliced
    out after the scan. Under an active mesh each stage buffer is pinned
    by the §6.5 stage-to-shard rule (micro-batch over ``data``, output
    lines over ``column``); without one the constraints are identity.

    Recurrent carries ride the same schedule: layer l's carry slab
    (m, rows, n_outputs_l) is shifted by l leading silent ticks so tick
    l + j feeds micro-batch j's carry rows, and the layer's per-tick
    flattened outputs are collected from ticks l .. l+m-1 into the next
    cycle's carry — the carry is per-row state, so it micro-batches
    exactly like the input volleys do.

    Bit-exact vs the barriered schedule for every backend and any M: a
    ragged ``B % M != 0`` batch is NO_SPIKE-padded to full micro-batches
    (padding rows carry silent state).
    """
    b = x.shape[0]
    n_layers = len(cfg.layers)
    if m * rows > b:             # ragged tail: NO_SPIKE rows are inert
        x = jnp.pad(x, ((0, m * rows - b), (0, 0)),
                    constant_values=int(coding.NO_SPIKE))
    xs = x.reshape(m, rows, x.shape[-1])
    if n_layers > 1:             # drain ticks flush the last micro-batches
        xs = jnp.pad(xs, ((0, n_layers - 1), (0, 0), (0, 0)),
                     constant_values=int(coding.NO_SPIKE))
    # per-layer carry slabs, tick-aligned: layer l sees micro-batch j's
    # carry at tick l + j, silent blocks during its warmup/drain ticks.
    carry_xs = []
    for i, (lc, c) in enumerate(zip(cfg.layers, carry_in)):
        if not lc.recurrent:
            carry_xs.append(None)
            continue
        c_full = c if c is not None else layer_mod.carry_init(lc, b)
        if m * rows > b:
            c_full = jnp.pad(c_full, ((0, m * rows - b), (0, 0)),
                             constant_values=int(coding.NO_SPIKE))
        cx = c_full.reshape(m, rows, lc.n_outputs)
        cx = jnp.pad(cx, ((i, n_layers - 1 - i), (0, 0), (0, 0)),
                     constant_values=int(coding.NO_SPIKE))
        carry_xs.append(cx)
    stage0 = tuple(layer_mod.stage_init(lc, rows) for lc in cfg.layers[1:])
    stage_axes = sharding_specs.tnn_stage_axes()
    carry_axes = sharding_specs.tnn_carry_axes()

    def tick(stage, xs_t):
        x_t, c_t = xs_t
        new_stage, wins, couts, out = [], [], [], None
        for i, (w, lc) in enumerate(zip(params, cfg.layers)):
            inp = x_t if i == 0 else stage[i - 1]
            out, win = layer_mod.layer_forward(w, inp, lc, c_t[i])
            wins.append(win)
            flat = out.reshape(rows, lc.n_outputs)
            couts.append(sharding_specs.maybe_wsc(flat, *carry_axes)
                         if lc.recurrent else None)
            if i + 1 < n_layers:
                new_stage.append(sharding_specs.maybe_wsc(flat,
                                                          *stage_axes))
        return tuple(new_stage), (out, tuple(wins), tuple(couts))

    _, (ys_out, ys_win, ys_carry) = jax.lax.scan(
        tick, stage0, (xs, tuple(carry_xs)))
    # layer l's tick-t output belongs to micro-batch t - l: the last
    # layer's valid outputs are ticks L-1 .. L-1+M-1, layer l's winners
    # (and carry blocks) ticks l .. l+M-1; outside is warmup/drain pad.
    out = ys_out[n_layers - 1:]
    out = out.reshape(m * rows, *out.shape[2:])[:b]
    # re-pin after reassembling micro-batches: XLA does not carry the
    # per-tick stage pins through the reshape, leaving the final (B, C, Q)
    # volley batch-REPLICATED on a data-sharded mesh (caught by the §7.2
    # layout auditor; identity without a mesh).
    _dp, _col = sharding_specs.tnn_stage_axes()
    out = sharding_specs.maybe_wsc(out, _dp, _col, None)
    winners = tuple(
        ys_win[i][i:i + m].reshape(m * rows, -1)[:b]
        for i in range(n_layers))
    carry_out = tuple(
        ys_carry[i][i:i + m].reshape(m * rows, lc.n_outputs)[:b]
        if lc.recurrent else None
        for i, lc in enumerate(cfg.layers))
    return ForwardResult(out, winners, carry_out, None)


def measured_densities(params: Sequence[jax.Array], volleys: jax.Array,
                       cfg: TNNNetwork):
    """Per-layer measured input densities for one concrete batch — each
    layer's density (the fraction of contributing lines its neuron banks
    see — layer 0 reflects the input encoding's sparsity, deeper layers
    the 1-WTA thinning, at most one hot line per column so density <=
    1/n_neurons there) recorded on the same activations one forward pass
    computes (§3.3 diagnostic). Host-side: entries are ``None``
    under jit (``layer_input_density``)."""
    return forward(params, volleys, cfg, with_densities=True).densities


def sparse_widths(cfg: TNNNetwork, first: int) -> Tuple[int, ...]:
    """Static per-layer compaction widths for a jitted sparse stack (§3.3).

    Layer 0 gets ``first`` — the caller's measured-and-bucketed active-line
    bound for its FEEDFORWARD receptive-field gather (the serve engine
    computes it host-side per step; see
    :func:`repro.core.compaction.bucket_width`). Deeper layers need no
    measurement: layer l consumes layer l-1's post-WTA lines, at most one
    active per block of ``Q_prev``, so an ``rf``-wide window covers at most
    ``(rf - 2) // Q_prev + 2`` blocks — a structural bound that can never
    drop an active line. A recurrent layer sees Q extra carry lines that
    are themselves a post-WTA volley of its own column — at most one
    active — so its width grows by exactly 1.
    """
    widths = [max(int(first), 1) + (1 if cfg.layers[0].recurrent else 0)]
    for prev, cur in zip(cfg.layers, cfg.layers[1:]):
        q, rf = prev.n_neurons, cur.rf_size
        bound = 1 if rf <= 1 else min(rf, (rf - 2) // q + 2, prev.n_columns)
        widths.append(max(bound, 1) + (1 if cur.recurrent else 0))
    return tuple(widths)


class StepResult(NamedTuple):
    """Everything one *learning* gamma cycle produces (:func:`step`).

    ``params``: per-layer post-STDP weights — the explicit weight state a
    learning service threads from step to step (nothing is closed over).
    ``out`` / ``winners`` / ``carry`` mirror :class:`ForwardResult`: the
    forward quantities are computed at the PRE-update weights (learning is
    applied after the cycle, like the hardware's post-WTA STDP datapath),
    so ``out`` is bit-exact with :func:`forward` at the same weights.
    """

    params: Tuple[jax.Array, ...]
    out: jax.Array
    winners: Tuple[jax.Array, ...]
    carry: Tuple[Optional[jax.Array], ...]


def step(params: Sequence[jax.Array], volleys: jax.Array, cfg: TNNNetwork,
         *, key: Optional[jax.Array] = None,
         carry: Optional[Sequence[Optional[jax.Array]]] = None
         ) -> StepResult:
    """Forward + layer-local minibatch STDP — THE learning entry point.

    One gamma cycle through the stack with every layer applying its own
    STDP update (:func:`repro.core.layer.layer_step`): layer l learns from
    the volley it actually saw this cycle (the previous layer's PRE-update
    output), so a single sweep advances the whole stack — greedy
    layer-local learning, no backward pass. ``carry`` threads recurrent
    state exactly like :func:`forward` (a recurrent layer's STDP slice
    includes its carry lines, so the recurrent weight columns learn under
    the same rule); the returned ``carry`` feeds the stream's next cycle.

    The schedule is barriered: a learning step reduces per-volley deltas
    across the whole batch (minibatch STDP), which is a batch-wide barrier
    by construction — pipelined micro-batching applies to pure forward
    steps only (DESIGN.md §5.5). All-``NO_SPIKE`` rows (a serving batch's
    free slots) contribute zero delta — no input spike means no capture /
    backoff / search case fires — so padding is inert for learning too;
    with the default ``"mean"`` reduction the batch size still sets the
    (deterministic) step scale.

    Args mirror :func:`forward`; ``key=None`` selects the deterministic
    expectation rule (replayable — the crash-recovery contract), a PRNG
    key the stochastic one. Returns :class:`StepResult`; a 1-D single
    volley drops the batch dim from every non-param field.
    """
    n_layers = len(cfg.layers)
    if carry is None:
        carry_in: Tuple[Optional[jax.Array], ...] = (None,) * n_layers
    else:
        if len(carry) != n_layers:
            raise ValueError(f"carry has {len(carry)} entries for "
                             f"{n_layers} layers")
        carry_in = tuple(carry)
    single = volleys.ndim == 1
    x = volleys[None, :] if single else volleys
    x = x.astype(jnp.int32)
    if single:
        carry_in = tuple(c[None, :] if c is not None and c.ndim == 1 else c
                         for c in carry_in)
    keys = (jax.random.split(key, n_layers)
            if key is not None else [None] * n_layers)
    new_params, winners_all, carry_out = [], [], []
    out = None
    for w, lc, lk, c in zip(params, cfg.layers, keys, carry_in):
        new_w, out, winners = layer_mod.layer_step(w, x, lc, lk, c)
        new_params.append(new_w)
        winners_all.append(winners)
        x = out.reshape(out.shape[0], lc.n_outputs)
        carry_out.append(x if lc.recurrent else None)
    res = StepResult(tuple(new_params), out, tuple(winners_all),
                     tuple(carry_out))
    if single:
        res = StepResult(
            params=res.params,
            out=res.out[0],
            winners=tuple(w[0] for w in res.winners),
            carry=tuple(c if c is None else c[0] for c in res.carry))
    return res


def network_step(params: Sequence[jax.Array], volleys: jax.Array,
                 cfg: TNNNetwork, key: Optional[jax.Array] = None
                 ) -> Tuple[Tuple[jax.Array, ...], jax.Array,
                            Tuple[jax.Array, ...]]:
    """Feedforward wrapper over :func:`step` (no carry threading; a 1-D
    volley keeps its promoted batch dim, the historical contract).
    Returns (new_params, last_out_times, per_layer_winners).
    """
    x = volleys[None, :] if volleys.ndim == 1 else volleys
    res = step(params, x, cfg, key=key)
    return res.params, res.out, res.winners


def train_network(params: Sequence[jax.Array], volleys: jax.Array,
                  cfg: TNNNetwork, batch_size: int = 1,
                  key: Optional[jax.Array] = None
                  ) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    """Greedy simultaneous training over a stream (M, n_inputs) of volleys.

    Returns (final_params, per_layer winners (M, C_l)).
    """

    def step(ps, batch, sk):
        new_ps, _, winners = network_step(ps, batch, cfg, sk)
        return new_ps, winners

    final, winners = layer_mod.scan_minibatches(step, tuple(params),
                                                volleys, batch_size, key)
    return final, tuple(w.reshape(volleys.shape[0], lc.n_columns)
                        for w, lc in zip(winners, cfg.layers))
