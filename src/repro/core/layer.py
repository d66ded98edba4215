"""Batched multi-column TNN layer (DESIGN.md §6).

A :class:`TNNLayer` is C independent columns side by side — the unit of
computation in layered TNNs (Smith [12, 13]; Nair et al. [7] tile the same
structure in RTL; Vellaisamy & Shen's SPU framework stacks them into
sensory-processing pipelines). Per gamma cycle:

  1. The layer receives a batch of B input volleys over ``n_inputs`` lines.
  2. Each column c reads its *receptive field* — a contiguous window of
     ``rf_size`` lines starting at ``c * rf_stride`` (stride defaults to
     the window size, i.e. disjoint tiling; overlap with smaller strides).
  3. All B x C x Q neurons integrate in one
     :func:`repro.core.neuron.fire_times_bank` dispatch (closed form, tick
     scan, or one fused Pallas launch over a (C, batch, neuron) grid).
  4. 1-WTA lateral inhibition runs vectorized over the (B, C) plane: per
     column, the earliest-firing neuron keeps its spike (ties -> lowest
     index, the hardware priority encoder); losers are silenced.
  5. Minibatch STDP (:func:`repro.core.stdp.stdp_update_column_minibatch`)
     accumulates per-volley updates across the batch dimension; at B=1 it
     is bit-identical to the online per-volley rule used by
     :func:`repro.core.column.column_step`.

Everything is functional (weights in, weights out) and jit/scan friendly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import coding, compaction, neuron, stdp
from repro.sharding import compat
from repro.sharding import specs as sharding_specs

#: axis entries for the in-layer sharding constraints (identity when no
#: mesh is active — see sharding.specs.maybe_wsc / tnn_volley_axes)
_COL, _DP, _ = sharding_specs.tnn_volley_axes()
#: axis entries for the recurrent carry (B, n_outputs): batch over DP,
#: flattened output lines over "column" (sharding.specs.tnn_carry_axes)
_CARRY = sharding_specs.tnn_carry_axes()
#: axis entries for a (C, Q, rf) weight stack: columns over "column"
#: (sharding.specs.tnn_param_axes) — the STDP output constraint, so an
#: updated weight stack keeps the tnn_param_pspec placement
_PARAM = sharding_specs.tnn_param_axes()


@dataclasses.dataclass(frozen=True)
class TNNLayer:
    """Static layer description; weights live in a (C, Q, rf_total) array."""

    n_columns: int
    rf_size: int
    n_neurons: int
    threshold: int
    t_steps: int
    dendrite: neuron.DendriteKind = "catwalk"
    k: int = 2
    w_max: int = 7
    #: receptive-field stride between adjacent columns; None = rf_size
    #: (disjoint windows). rf_stride < rf_size gives overlapping fields.
    rf_stride: Optional[int] = None
    #: recurrent input path (DESIGN.md §6.1): each column additionally sees
    #: its OWN Q post-WTA output lines from the previous gamma cycle,
    #: appended after the feedforward receptive field — Q extra columns in
    #: the weight plane, so weights become (C, Q, rf_size + Q). A silent
    #: (all-NO_SPIKE) carry makes the cycle exactly feedforward: silent
    #: lines launch no ramp and contribute nothing to any neuron.
    recurrent: bool = False
    #: neuron-bank engine (DESIGN.md §2/§3.3): the sparse engines ("event",
    #: "pallas_compact") compact the post-gather (C, B, rf) tensor in ONE
    #: call inside fire_times_bank, so one relocation serves all columns.
    backend: neuron.Backend = "auto"
    #: static compaction width for the sparse engines under jit (§3.3):
    #: active lines per (column, volley) after the receptive-field gather.
    #: None = measured with concrete inputs, uncompacted solve when traced.
    #: Traced callers must guarantee it covers the batch (the serve engine
    #: measures + buckets host-side; see network.sparse_widths).
    n_active_max: Optional[int] = None
    stdp: stdp.STDPConfig = dataclasses.field(default_factory=stdp.STDPConfig)
    #: minibatch STDP reduction: "mean" (default) or "sum".
    stdp_reduction: str = "mean"

    @property
    def stride(self) -> int:
        return self.rf_size if self.rf_stride is None else self.rf_stride

    @property
    def n_inputs(self) -> int:
        """Input lines the layer consumes (last window end-aligned)."""
        return self.stride * (self.n_columns - 1) + self.rf_size

    @property
    def n_outputs(self) -> int:
        """Output lines the layer produces (one per neuron, flattened)."""
        return self.n_columns * self.n_neurons

    @property
    def rf_total(self) -> int:
        """Dendritic inputs per neuron: rf_size + Q recurrent lines."""
        return self.rf_size + (self.n_neurons if self.recurrent else 0)

    def rf_index(self) -> jax.Array:
        """(C, rf_size) int32 input-line ids per column."""
        starts = jnp.arange(self.n_columns, dtype=jnp.int32) * self.stride
        return starts[:, None] + jnp.arange(self.rf_size, dtype=jnp.int32)

    def neuron_config(self) -> neuron.NeuronConfig:
        return neuron.NeuronConfig(
            n_inputs=self.rf_total, threshold=self.threshold,
            t_steps=self.t_steps, dendrite=self.dendrite, k=self.k)

    def column_config(self):
        """Single-column view (for per-column tooling / equivalence tests)."""
        from repro.core import column
        return column.ColumnConfig(
            n_inputs=self.rf_total, n_neurons=self.n_neurons,
            threshold=self.threshold, t_steps=self.t_steps,
            dendrite=self.dendrite, k=self.k, w_max=self.w_max,
            stdp=self.stdp, backend=self.backend)


def init_layer(key: jax.Array, cfg: TNNLayer) -> jax.Array:
    """Random initial weights (C, Q, rf_total) uniform over [0, w_max]."""
    return jax.random.uniform(
        key, (cfg.n_columns, cfg.n_neurons, cfg.rf_total),
        minval=0.0, maxval=float(cfg.w_max))


def carry_init(cfg: TNNLayer, batch: int) -> jax.Array:
    """All-silent recurrent carry ``(batch, n_outputs)`` for a layer.

    The previous-cycle output volley fed to the first gamma cycle of a
    stream: all-``NO_SPIKE``, so cycle 0 of a recurrent layer is bit-exact
    with the same layer run feedforward (silent lines are inert)."""
    return jnp.full((batch, cfg.n_outputs), coding.NO_SPIKE, jnp.int32)


def stage_init(cfg: TNNLayer, batch: int) -> jax.Array:
    """All-``NO_SPIKE`` pipeline stage buffer ``(batch, n_inputs)``.

    The inert warmup/drain carry for gamma-cycle pipelining (DESIGN.md
    §5.4): silent lines launch no RNL ramp, so a layer fed this buffer
    fires no neuron and emits an all-``NO_SPIKE`` volley — padding
    propagates as padding through the whole stack."""
    return jnp.full((batch, cfg.n_inputs), coding.NO_SPIKE, jnp.int32)


def _gather_rf(volleys: jax.Array, cfg: TNNLayer,
               carry: Optional[jax.Array] = None) -> jax.Array:
    """(B, n_inputs) volleys -> (C, B, rf_total) per-column slices.

    For a recurrent layer, each column's slice is its feedforward window
    followed by that column's OWN Q previous-cycle output lines from
    ``carry`` (B, n_outputs); ``carry=None`` feeds the silent volley.
    """
    rf = volleys[:, cfg.rf_index()]           # (B, C, rf)
    rf = jnp.swapaxes(rf, 0, 1)               # (C, B, rf)
    if not cfg.recurrent:
        return rf
    b = volleys.shape[0]
    if carry is None:
        carry = carry_init(cfg, b)
    rec = carry.reshape(b, cfg.n_columns, cfg.n_neurons)
    rec = jnp.swapaxes(rec, 0, 1)             # (C, B, Q)
    return jnp.concatenate([rf, rec], axis=-1)  # (C, B, rf + Q)


def layer_input_density(volleys: jax.Array, cfg: TNNLayer,
                        carry: Optional[jax.Array] = None):
    """Measured fraction of contributing lines across the layer's
    receptive fields (host diagnostic; ``None`` under jit).

    Overlapping fields count shared lines once per column — this is the
    density the neuron banks actually see.
    """
    if compat.is_tracer(volleys):
        return None
    v = volleys[None, :] if volleys.ndim == 1 else volleys
    if carry is not None and carry.ndim == 1:
        carry = carry[None, :]
    return compaction.measured_density(_gather_rf(v, cfg, carry),
                                       cfg.t_steps)


def layer_forward(weights: jax.Array, volleys: jax.Array, cfg: TNNLayer,
                  carry: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Run one gamma cycle for a batch of volleys.

    Args:
      weights: (C, Q, rf_total) float; rounded to ints (hardware registers).
      volleys: (B, n_inputs) int32 spike volleys — or (n_inputs,) for one.
      carry: previous-cycle output volley (B, n_outputs) int32 for a
        recurrent layer (1-D for a single volley); None = silent carry.
        Must be None for a non-recurrent layer.

    Returns:
      (out_times, winners): out_times (B, C, Q) int32 post-WTA spike times
      (NO_SPIKE for losers); winners (B, C) int32 per-column winner index,
      -1 where no neuron in the column fired. 1-D input gives (C, Q)/(C,).
      ``out_times.reshape(B, n_outputs)`` is the next cycle's carry.
    """
    if carry is not None and not cfg.recurrent:
        raise ValueError("carry given for a non-recurrent layer")
    single = volleys.ndim == 1
    if single:
        volleys = volleys[None, :]
        if carry is not None and carry.ndim == 1:
            carry = carry[None, :]
    if carry is not None:
        # pin the carry like a stage buffer: batch over DP, output lines
        # over "column" (sharding.specs.tnn_carry_axes; identity w/o mesh).
        carry = sharding_specs.maybe_wsc(carry, *_CARRY)
    # named scopes group the device ops by stage in a profile (op names
    # carry the scope); they change nothing that is computed
    with jax.named_scope("gather"):
        w_int = jnp.round(weights).astype(jnp.int32)
        times_rf = _gather_rf(volleys, cfg, carry)            # (C, B, rft)
        # under an active mesh, pin the (columns, neurons) plane: columns
        # over "column", batch over DP (DESIGN.md §6.4; identity without a
        # mesh). This is also the exact layout the shard_map Pallas fast
        # path consumes (kernels/rnl_shard mirrors these entries via
        # specs.ambient_fit), so when fire_times_bank takes that path no
        # resharding happens here.
        times_rf = sharding_specs.maybe_wsc(times_rf, _COL, _DP, None)
    with jax.named_scope("bank"):
        fire = neuron.fire_times_bank(times_rf, w_int, cfg.neuron_config(),
                                      backend=cfg.backend,
                                      n_active_max=cfg.n_active_max)  # (C, B, Q)
        fire = sharding_specs.maybe_wsc(fire, _COL, _DP, None)
    with jax.named_scope("wta"):
        fire = jnp.swapaxes(fire, 0, 1)                       # (B, C, Q)
        # vectorized 1-WTA over the (B, C) plane; argmin's first-minimum
        # rule is the tie-break-to-lowest-index priority encoder.
        any_fire = jnp.any(coding.is_spike(fire), axis=-1)    # (B, C)
        winners = jnp.argmin(fire, axis=-1).astype(jnp.int32)
        winners = jnp.where(any_fire, winners, -1)
        winners = sharding_specs.maybe_wsc(winners, _DP, _COL)
        lane = jnp.arange(cfg.n_neurons, dtype=jnp.int32)
        out = jnp.where(lane == winners[..., None], fire, coding.NO_SPIKE)
        out = sharding_specs.maybe_wsc(out, _DP, _COL, None)
    if single:
        return out[0], winners[0]
    return out, winners


def layer_step(weights: jax.Array, volleys: jax.Array, cfg: TNNLayer,
               key: Optional[jax.Array] = None,
               carry: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Forward + minibatch STDP. Returns (new_weights, out_times, winners).

    Per-volley STDP deltas are evaluated at the shared pre-step weights and
    accumulated across the batch (``cfg.stdp_reduction``); each column
    learns only from its own receptive-field slice and WTA outcome. For a
    recurrent layer the STDP input slice includes the carry lines, so the
    recurrent weight columns learn under the same rule as feedforward ones.
    """
    if volleys.ndim == 1:
        volleys = volleys[None, :]
        if carry is not None and carry.ndim == 1:
            carry = carry[None, :]
    out_times, winners = layer_forward(weights, volleys, cfg, carry)
    with jax.named_scope("stdp"):
        times_rf = _gather_rf(volleys, cfg, carry)            # (C, B, rft)
        times_rf = sharding_specs.maybe_wsc(times_rf, _COL, _DP, None)
        out_cb = jnp.swapaxes(out_times, 0, 1)                # (C, B, Q)
        win_cb = jnp.swapaxes(winners, 0, 1)                  # (C, B)
        ckeys = (jax.random.split(key, cfg.n_columns)
                 if key is not None else None)

        def one_column(w, in_t, out_t, win, ck):
            return stdp.stdp_update_column_minibatch(
                w, in_t, out_t, win, cfg.stdp, ck,
                reduction=cfg.stdp_reduction)

        if ckeys is None:
            new_w = jax.vmap(
                lambda w, t, o, g: one_column(w, t, o, g, None))(
                weights, times_rf, out_cb, win_cb)
        else:
            new_w = jax.vmap(one_column)(weights, times_rf, out_cb, win_cb,
                                         ckeys)
        # pin the updated stack where tnn_param_pspec placed the input
        # stack (identity without a mesh): a learning service's weights
        # never drift off their column shards across steps (DESIGN.md
        # §6.4).
        new_w = sharding_specs.maybe_wsc(new_w, *_PARAM)
    return new_w, out_times, winners


def scan_minibatches(step_fn, carry, volleys: jax.Array, batch_size: int,
                     key: Optional[jax.Array]):
    """Stream-batching scaffold shared by train_layer / train_network.

    Reshapes a (M, n) volley stream into M // batch_size sequential
    minibatches (M must be divisible) and lax.scans
    ``step_fn(carry, batch, key_or_None) -> (carry, ys)`` over them.
    """
    m = volleys.shape[0]
    if m % batch_size != 0:
        raise ValueError(f"stream length {m} not divisible by "
                         f"batch_size {batch_size}")
    steps = m // batch_size
    batches = volleys.reshape(steps, batch_size, volleys.shape[-1])
    keys = (jnp.zeros((steps, 2), jnp.uint32) if key is None
            else jax.random.split(key, steps))
    use_key = key is not None

    def step(c, xs):
        batch, sk = xs
        return step_fn(c, batch, sk if use_key else None)

    return jax.lax.scan(step, carry, (batches, keys))


def train_layer(weights: jax.Array, volleys: jax.Array, cfg: TNNLayer,
                batch_size: int = 1, key: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Train over a stream of volleys (M, n_inputs) via lax.scan.

    The stream is processed as M // batch_size sequential minibatches
    (M must be divisible); batch_size=1 is the classic online rule.

    Returns (final_weights, winners (M, C)).
    """

    def step(w, batch, sk):
        new_w, _, winners = layer_step(w, batch, cfg, sk)
        return new_w, winners

    final_w, winners = scan_minibatches(step, weights, volleys, batch_size,
                                        key)
    return final_w, winners.reshape(volleys.shape[0], cfg.n_columns)
