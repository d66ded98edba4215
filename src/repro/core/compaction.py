"""Spike compaction: relocate active lines into a dense prefix (DESIGN.md §3.3).

The paper's silicon wins by *relocating* the sparse subset of spiking
dendritic inputs into a dense cluster before accumulation (the unary top-k
CAS network). This module is the software analogue of that relocation for
the evaluation engines: per volley, gather the lines that can actually
contribute during the gamma cycle — ``times[i] < t_steps`` — into a dense
prefix of width ``n_active_max``, keeping a line-index map so synaptic
weights can be gathered to match. Silent / out-of-window lines are pushed
past the prefix and padded with ``NO_SPIKE``, which is inert in every
engine (a padded line never raises a ramp bit).

Consumers:

  * ``backend="event"``  — the exact sorted-breakpoint engine in
    :mod:`repro.core.neuron` sorts ``2s`` breakpoints instead of ``2n``.
  * ``backend="pallas_compact"`` — the spike-compacted Pallas tick sweep in
    :mod:`repro.kernels.rnl_neuron` runs over the compacted width ``s``
    instead of ``n`` (and cuts its tick loop at the last breakpoint).

Everything is shape-polymorphic over leading batch axes, so one call
compacts a whole ``(C, B, rf)`` receptive-field gather — one compaction
serves all columns of a :class:`repro.core.layer.TNNLayer`.

Width selection is data-dependent, hence incompatible with tracing: under
``jit`` callers must pass an explicit static ``n_active_max`` (see
:func:`bucket_width` for a recompile-bounded choice); with concrete inputs
the width is measured exactly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import coding
from repro.sharding import compat


def active_mask(times: jax.Array, t_steps: int) -> jax.Array:
    """Lines that can contribute a ramp bit within the gamma cycle.

    A line is *active* iff ``times < t_steps``: ``NO_SPIKE`` lines and
    spikes at/after the cycle end never assert a bit for ``t in [0, T)``.
    """
    return jnp.asarray(times) < jnp.int32(t_steps)


def measured_density(times, t_steps: int | None = None):
    """Fraction of active lines, or ``None`` when ``times`` is a tracer.

    With ``t_steps`` given, "active" means contributing-within-the-cycle
    (``times < t_steps``); without it, simply non-``NO_SPIKE``. Returns a
    Python float for host-side diagnostics
    (:func:`repro.core.network.measured_densities`); under ``jit`` the
    value is unknowable, hence ``None``.
    """
    if compat.is_tracer(times):
        return None
    times = jnp.asarray(times)
    if times.size == 0:
        return 0.0
    bound = jnp.int32(t_steps) if t_steps is not None else coding.NO_SPIKE
    return float(jnp.mean((times < bound).astype(jnp.float32)))


def max_active(times, t_steps: int):
    """Max per-volley active-line count, or ``None`` under tracing."""
    if compat.is_tracer(times):
        return None
    mask = active_mask(times, t_steps)
    if mask.size == 0:
        return 0
    return int(jnp.max(jnp.sum(mask.astype(jnp.int32), axis=-1)))


#: Vector-lane width the compacted-shape ladder aligns to at/above one
#: lane (mirrors ``repro.kernels.common.LANE``; defined locally so core
#: never imports the kernels package).
LANE_WIDTH = 128


def bucket_width(s: int, quantum: int = 8, lane: int = LANE_WIDTH) -> int:
    """Snap a measured width onto the lane-aligned bucket ladder.

    Below one vector lane the ladder is the power-of-two multiples of
    ``quantum`` (8, 16, 32, 64, 128); at or above ``lane`` it switches to
    lane multiples (128, 256, 384, ...). Two properties fall out:

      * jit variants stay few — O(log lane) small shapes plus O(n / lane)
        large ones — when the measured width drifts between batches (the
        serve engine's per-width variants are keyed on this);
      * every bucket >= ``lane`` is lane-aligned, so the ``pallas_compact``
        tick sweep reads full vector registers with no ragged tail
        (DESIGN.md §6.4).
    """
    s = max(int(s), 1)
    if s > lane:
        return -(-s // lane) * lane
    width = quantum
    while width < s:
        width *= 2
    return min(width, lane)


@dataclasses.dataclass
class CompactVolleys:
    """Dense-prefix view of a volley batch.

    times:      (..., s) int32 — active lines first (original line order
                preserved), then ``NO_SPIKE`` padding.
    line_index: (..., s) int32 — original line id of each slot (padding
                slots point at arbitrary inactive lines; their ``NO_SPIKE``
                time keeps them inert regardless of the weight gathered).
    n_active:   (...,)  int32 — true active count per volley.
    overflow:   (...,)  int32 — active lines dropped because ``s`` was too
                small (always 0 when the width was measured, not forced).
    """

    times: jax.Array
    line_index: jax.Array
    n_active: jax.Array
    overflow: jax.Array

    @property
    def width(self) -> int:
        return self.times.shape[-1]


def compact_volleys(times: jax.Array, t_steps: int,
                    n_active_max: int | None = None) -> CompactVolleys:
    """Gather each volley's active lines into a dense prefix.

    Args:
      times: (..., n) int32 spike times.
      t_steps: gamma-cycle length (defines "active", see
        :func:`active_mask`).
      n_active_max: static compacted width. ``None`` measures the exact
        max over the batch (concrete inputs only — raises under tracing).

    Returns:
      :class:`CompactVolleys` of width ``min(n_active_max, n)``.
    """
    times = jnp.asarray(times).astype(jnp.int32)
    n = times.shape[-1]
    mask = active_mask(times, t_steps)
    n_act = jnp.sum(mask.astype(jnp.int32), axis=-1)
    if n_active_max is None:
        if compat.is_tracer(times):
            raise ValueError(
                "compact_volleys under jit needs a static n_active_max "
                "(measure + bucket_width outside the traced region)")
        n_active_max = max(int(jnp.max(n_act)) if times.size else 0, 1)
    s = min(int(n_active_max), n) if n > 0 else 1
    # stable argsort of the inactive flag: active line ids first, original
    # order preserved — this IS the relocation permutation (paper Fig. 5),
    # computed per volley instead of wired as a CAS network.
    order = jnp.argsort(jnp.where(mask, 0, 1).astype(jnp.int32), axis=-1)
    line_index = order[..., :s]
    times_c = jnp.take_along_axis(times, line_index, axis=-1)
    # force padding slots inert even if a caller-forced width dropped lines
    slot = jnp.arange(s, dtype=jnp.int32)
    times_c = jnp.where(slot < n_act[..., None], times_c, coding.NO_SPIKE)
    overflow = jnp.maximum(n_act - s, 0)
    return CompactVolleys(times=times_c, line_index=line_index,
                          n_active=n_act, overflow=overflow)


def gather_weights(weights: jax.Array, line_index: jax.Array) -> jax.Array:
    """Per-volley weight gather matching a compaction's line-index map.

    Args:
      weights:    (..., Q, n) synaptic weights.
      line_index: (..., B, s) from :func:`compact_volleys`.

    Returns:
      (..., B, Q, s): ``out[..., b, q, j] = weights[..., q, index[b, j]]``.
    """
    w = jnp.asarray(weights)
    return jnp.take_along_axis(w[..., None, :, :],
                               line_index[..., :, None, :], axis=-1)
