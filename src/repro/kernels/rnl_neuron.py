"""Pallas TPU kernel: fused SRM0-RNL neuron bank.

Fuses the whole neuron pipeline — RNL response generation (Eq. 1), dendrite
accumulation (full-PC or Catwalk top-k-clipped), soma threshold, fire-time
detection — over a (batch x neurons) tile, sweeping gamma-cycle ticks in a
``fori_loop`` so the bit-plane (B, Q, n) working set stays in VMEM and HBM
traffic is one read of spike times/weights + one write of fire times.

Every entry point bounds its tick sweep by the batch's *last breakpoint
tick* ``min(t_steps, max(times + w))`` — an SMEM scalar operand computed in
XLA outside the launch (one per column block in the layer kernel) — so
short-ramp / sparse workloads stop as soon as no line can still raise a
bit, on every grid tile.

Three entry points (DESIGN.md §3.2, §3.3):

  * :func:`rnl_fire_times` — one neuron bank, grid (batch tiles, neuron
    tiles). This is the ``backend="pallas"`` engine behind
    :func:`repro.core.neuron.fire_times_bank`.
  * :func:`rnl_fire_times_layer` — C independent columns in one launch,
    grid (column blocks, neuron tiles, 128-slot tiles), lane-dense: slots
    on the lanes, neurons on the sublanes; serves
    :class:`repro.core.layer.TNNLayer` without a host-side column loop.
  * :func:`rnl_fire_times_compact` — the spike-compacted fast path
    (``backend="pallas_compact"``): volleys arrive with their active lines
    relocated to a dense prefix of width ``s`` (core/compaction.py — the
    software analogue of the paper's unary top-k relocation) and weights
    pre-gathered per volley, so the sweep's inner width is the active-line
    budget ``s`` instead of ``n``.

The bank/layer kernels optionally emit a second output: per-(volley,
neuron) *clip-event* counts (ticks where the raw popcount exceeded k — the
paper's sparsity-violation diagnostic), fused into the same tick sweep at
no extra HBM read. Early exit cannot change clip counts: past the last
breakpoint every popcount is zero.

Block shapes (bank; ``q_tile`` from :func:`_q_tiling` — the whole padded Q
up to 128 neurons, else 128):
  t_hi    (1,)             int32 SMEM (shared by all tiles)
  times   (B_TILE, n)      int32
  weights (q_tile, n)      int32
  fire    (B_TILE, q_tile) int32 out   [+ clip (B_TILE, q_tile) int32 out]

Block shapes (layer; ``c_tile`` columns from :func:`_layer_tiling`, the
wrapper moving the batch to the lanes, padded to 128, and Q to the last
weight axis):
  t_hi    (C_pad / c_tile,)      int32 SMEM (one per column block)
  times   (c_tile, n, 128)       int32  lines on sublanes, slots on lanes
  weights (c_tile, n, q_tile)    int32
  fire    (c_tile, q_tile, 128)  int32 out   [+ clip, same shape]
  scratch (n, q_tile, 128)       int32  per-line ramp ends of one column

Block shapes (compact): times (B_TILE, s); weights (B_TILE, q_tile, s) —
per-volley after the compaction gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.core.coding import NO_SPIKE

#: plain Python int (Pallas kernels may not capture array constants)
NO_SPIKE_INT = int(NO_SPIKE)

B_TILE = 8

#: VMEM for one set of the layer kernel's blocks (times, weights, outputs);
#: the pipeline double-buffers it
LAYER_BLOCK_BYTES = 1 << 20


def _q_tiling(qsz: int) -> tuple[int, int]:
    """(padded Q, neuron tile) for the ``(B_TILE, Q)`` output block (the
    layer kernel's ``(n, Q)`` weight block).

    Mosaic needs the block's last dim to be a multiple of the 128-lane
    width or the whole array dim: a bank of up to 128 neurons is one tile
    spanning the whole padded Q (padded to the sublane quantum, since Q is
    also the second-to-last dim of the weight block); wider banks pad to
    whole lanes and tile at 128.
    """
    q_pad = common.round_up(qsz, common.SUBLANE)
    if q_pad <= common.LANE:
        return q_pad, q_pad
    return common.round_up(qsz, common.LANE), common.LANE


def _tick_sweep(times, w, *, t_hi, threshold, k):
    """Shared tick loop: (B, n) times x (Q, n) weights -> fire/clip (B, Q).

    ``t_hi`` is a traced scalar loop bound (ticks >= t_hi carry no ramp
    bits, so stopping there is exact); ``w`` may also be (B, Q, n) for the
    compacted path's per-volley weights.
    """

    def tick(t, carry):
        pot, fired, clip = carry
        rel = t - times[:, None, :]                   # (B, 1, n)
        if w.ndim == 2:
            active = (rel >= 0) & (rel < w[None, :, :])    # (B, Q, n)
        else:
            active = (rel >= 0) & (rel < w)                # per-volley w
        raw = jnp.sum(active.astype(jnp.int32), axis=-1)   # (B, Q)
        if k is not None:
            inc = jnp.minimum(raw, k)                 # Catwalk clip
            clip = clip + (raw > k).astype(jnp.int32)
        else:
            inc = raw
        pot = pot + inc
        newly = (pot >= threshold) & (fired == NO_SPIKE_INT)
        fired = jnp.where(newly, t, fired)
        return pot, fired, clip

    b = times.shape[0]
    q = w.shape[0] if w.ndim == 2 else w.shape[1]
    pot0 = jnp.zeros((b, q), jnp.int32)
    fire0 = jnp.full((b, q), NO_SPIKE_INT, jnp.int32)
    clip0 = jnp.zeros((b, q), jnp.int32)
    _, fired, clip = jax.lax.fori_loop(0, t_hi, tick, (pot0, fire0, clip0))
    return fired, clip


def _sweep_bound(contrib, t_steps: int, threshold: int,
                 axis: int | None = None) -> jax.Array:
    """int32 SMEM operand: first tick past the last possible ramp bit,
    clamped to [0, t_steps] — one scalar ``(1,)`` for the whole launch, or
    one per leading row of ``contrib`` with ``axis=1`` (the layer kernel's
    column blocks). ``contrib`` holds per-line ``times + w`` where the line
    is active (``times < t_steps``) and 0 elsewhere.

    threshold <= 0 is met by the zero initial potential, so the soma fires
    at tick 0 even with no input — at least one tick must run for the
    bounded sweep to stay bit-exact with the full scan.
    """
    t_hi = jnp.minimum(jnp.int32(t_steps), jnp.max(contrib, axis=axis))
    floor = min(1, t_steps) if threshold <= 0 else 0
    return jnp.maximum(t_hi, floor).astype(jnp.int32).reshape(-1)


def _smem_scalar_spec():
    """Whole-array SMEM spec for the shared t_hi scalar (any grid)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _rnl_kernel(thi_ref, times_ref, weights_ref, out_ref, *,
                threshold, k):
    fired, _ = _tick_sweep(times_ref[...], weights_ref[...],
                           t_hi=thi_ref[0], threshold=threshold, k=k)
    out_ref[...] = fired


def _rnl_clip_kernel(thi_ref, times_ref, weights_ref, out_ref, clip_ref, *,
                     threshold, k):
    fired, clip = _tick_sweep(times_ref[...], weights_ref[...],
                              t_hi=thi_ref[0], threshold=threshold, k=k)
    out_ref[...] = fired
    clip_ref[...] = clip


def _rnl_compact_kernel(thi_ref, times_ref, weights_ref, out_ref, *,
                        threshold, k):
    fired, _ = _tick_sweep(times_ref[...], weights_ref[...],
                           t_hi=thi_ref[0], threshold=threshold, k=k)
    out_ref[...] = fired


@functools.partial(jax.jit,
                   static_argnames=("t_steps", "threshold", "k", "with_clip"))
def rnl_fire_times(times: jax.Array, weights: jax.Array, *, t_steps: int,
                   threshold: int, k: int | None = None,
                   with_clip: bool = False):
    """Fire times of a neuron bank.

    Args:
      times:   (B, n) int32 input spike times (NO_SPIKE = silent line).
      weights: (Q, n) int32 synaptic weights (one row per neuron).
      t_steps: gamma-cycle length.
      threshold: firing threshold.
      k: None -> full-PC dendrite; int -> Catwalk top-k clipped dendrite.
      with_clip: also return per-(volley, neuron) clip-event counts.

    Returns:
      (B, Q) int32 fire times (NO_SPIKE where the neuron stays silent);
      with ``with_clip`` a ``(fire, clip)`` tuple, clip (B, Q) int32 counts
      of ticks whose raw popcount exceeded k (all-zero when k is None).
    """
    bsz, n = times.shape
    qsz, n2 = weights.shape
    assert n == n2, (times.shape, weights.shape)
    b_pad = common.round_up(bsz, B_TILE)
    q_pad, q_tile = _q_tiling(qsz)
    # pad silent lines / zero-weight neurons: they never fire, harmless
    times_p = jnp.pad(times, ((0, b_pad - bsz), (0, 0)),
                      constant_values=int(NO_SPIKE))
    weights_p = jnp.pad(weights, ((0, q_pad - qsz), (0, 0)))
    # early-exit bound: per-line worst-case last breakpoint (max over
    # neurons of times + w), reduced to one scalar for the whole launch
    w_line = jnp.max(weights_p, axis=0)                        # (n,)
    t_hi = _sweep_bound(
        jnp.where(times_p < t_steps, times_p + w_line[None, :], 0), t_steps,
        threshold)

    grid = (b_pad // B_TILE, q_pad // q_tile)
    in_specs = [
        _smem_scalar_spec(),
        pl.BlockSpec((B_TILE, n), lambda b, q: (b, 0)),
        pl.BlockSpec((q_tile, n), lambda b, q: (q, 0)),
    ]
    out_spec = pl.BlockSpec((B_TILE, q_tile), lambda b, q: (b, q))
    if not with_clip:
        out = pl.pallas_call(
            functools.partial(_rnl_kernel, threshold=threshold, k=k),
            out_shape=jax.ShapeDtypeStruct((b_pad, q_pad), jnp.int32),
            grid=grid, in_specs=in_specs, out_specs=out_spec,
            name="rnl_fire_times",
            interpret=common.use_interpret(),
        )(t_hi, times_p, weights_p)
        return out[:bsz, :qsz]
    fire, clip = pl.pallas_call(
        functools.partial(_rnl_clip_kernel, threshold=threshold, k=k),
        out_shape=[jax.ShapeDtypeStruct((b_pad, q_pad), jnp.int32),
                   jax.ShapeDtypeStruct((b_pad, q_pad), jnp.int32)],
        grid=grid, in_specs=in_specs, out_specs=[out_spec, out_spec],
        name="rnl_fire_times",
        interpret=common.use_interpret(),
    )(t_hi, times_p, weights_p)
    return fire[:bsz, :qsz], clip[:bsz, :qsz]


def _layer_tiling(csz: int, n: int, q_tile: int,
                  n_out: int) -> tuple[int, int]:
    """(columns per block, column blocks) for the layer kernel.

    As many columns as fit :data:`LAYER_BLOCK_BYTES` of (8, 128)-tiled
    VMEM for one set of blocks — a column's ``(n, 128)`` times, its
    ``(n, q_tile)`` weights and its ``(q_tile, 128)`` outputs — spread
    evenly over the fewest blocks, so the silent padding columns number
    fewer than the blocks.
    """
    sub, lane = common.SUBLANE, common.LANE
    col_bytes = 4 * common.round_up(n, sub) * (lane + common.round_up(
        q_tile, lane)) + 4 * n_out * q_tile * lane
    c_max = max(1, LAYER_BLOCK_BYTES // col_bytes)
    n_blocks = common.cdiv(csz, c_max)
    return common.cdiv(csz, n_blocks), n_blocks


def _rnl_layer_kernel(thi_ref, times_ref, w_ref, *refs, n_columns, threshold,
                      k):
    """One column block: ``c_tile`` columns, ``q_tile`` neurons on the
    sublanes, 128 slots on the lanes.

    Per column, the ramp ends ``hi[i, q] = times[i] + max(w[i, q], 0)`` of
    every line are laid out once as ``(q_tile, 128)`` slabs in the ``hi_ref``
    scratch. Each tick then counts ``raw = #{i: times_i <= t} -
    #{i: hi_iq <= t}`` (exact: ``times_i <= hi_iq``) by elementwise adds of
    whole slabs over the line axis — the first term is shared by every
    neuron and reduces over sublanes only; nothing reduces across lanes.
    """
    if len(refs) == 3:
        out_ref, clip_ref, hi_ref = refs
    else:
        (out_ref, hi_ref), clip_ref = refs, None
    c_tile, _, lanes = times_ref.shape
    q_tile = out_ref.shape[1]
    blk = pl.program_id(0)
    zeros = jnp.zeros((q_tile, lanes), jnp.int32)

    def column(c, carry):
        w = jnp.maximum(w_ref[c], 0)                          # (n, q_tile)
        hi_ref[...] = times_ref[c][:, None, :] + w[:, :, None]

        def tick(t, st):
            pot, fired, clip = st
            started = jnp.sum((times_ref[c] <= t).astype(jnp.int32), axis=0,
                              keepdims=True)                  # (1, lanes)
            ended = jnp.sum((hi_ref[...] <= t).astype(jnp.int32), axis=0)
            raw = started - ended                             # (q_tile, lanes)
            if k is not None:
                inc = jnp.minimum(raw, k)                     # Catwalk clip
                clip = clip + (raw > k).astype(jnp.int32)
            else:
                inc = raw
            pot = pot + inc
            newly = (pot >= threshold) & (fired == NO_SPIKE_INT)
            return pot, jnp.where(newly, t, fired), clip

        fire0 = jnp.full((q_tile, lanes), NO_SPIKE_INT, jnp.int32)
        _, fired, clip = jax.lax.fori_loop(0, thi_ref[blk], tick,
                                           (zeros, fire0, zeros))
        out_ref[c] = fired
        if clip_ref is not None:
            clip_ref[c] = clip
        return carry

    # the last block's padding columns are silent: skip their sweep
    jax.lax.fori_loop(0, jnp.minimum(c_tile, n_columns - blk * c_tile),
                      column, 0)


@functools.partial(jax.jit,
                   static_argnames=("t_steps", "threshold", "k", "with_clip"))
def rnl_fire_times_layer(times: jax.Array, weights: jax.Array, *,
                         t_steps: int, threshold: int, k: int | None = None,
                         with_clip: bool = False):
    """Fire times of C independent neuron banks (a TNN layer of columns).

    One launch, grid (column blocks, neuron tiles, 128-slot tiles): column c
    pairs volley slice ``times[c]`` with weight bank ``weights[c]`` — the
    receptive-field gather happens upstream in :mod:`repro.core.layer`. The
    batch moves to the lanes (padded to 128 with silent slots) and back, and
    the weights to ``(C, n, Q)``, in XLA around the kernel.

    Args:
      times:   (C, B, n) int32 per-column input spike times.
      weights: (C, Q, n) int32 per-column synaptic weights.
      with_clip: also return clip-event counts.

    Returns:
      (C, B, Q) int32 fire times; with ``with_clip`` a ``(fire, clip)``
      tuple of that shape.
    """
    csz, bsz, n = times.shape
    c2, qsz, n2 = weights.shape
    assert csz == c2 and n == n2, (times.shape, weights.shape)
    n_out = 2 if with_clip else 1
    b_pad = common.round_up(bsz, common.LANE)
    q_pad, q_tile = _q_tiling(qsz)
    c_tile, n_blocks = _layer_tiling(csz, n, q_tile, n_out)
    c_pad = c_tile * n_blocks
    times_p = jnp.pad(times, ((0, c_pad - csz), (0, b_pad - bsz), (0, 0)),
                      constant_values=int(NO_SPIKE))
    weights_p = jnp.pad(weights, ((0, c_pad - csz), (0, q_pad - qsz), (0, 0)))
    # early-exit bound per column block: its last breakpoint over every
    # line, slot and neuron of its columns
    w_line = jnp.max(weights_p, axis=1)                        # (C_pad, n)
    t_hi = _sweep_bound(
        jnp.where(times_p < t_steps, times_p + w_line[:, None, :],
                  0).reshape(n_blocks, -1), t_steps, threshold, axis=1)

    out_spec = pl.BlockSpec((c_tile, q_tile, common.LANE),
                            lambda c, q, b: (c, q, b))
    out_shape = jax.ShapeDtypeStruct((c_pad, q_pad, b_pad), jnp.int32)
    outs = pl.pallas_call(
        functools.partial(_rnl_layer_kernel, n_columns=csz,
                          threshold=threshold, k=k),
        out_shape=[out_shape] * n_out,
        grid=(n_blocks, q_pad // q_tile, b_pad // common.LANE),
        in_specs=[
            _smem_scalar_spec(),
            pl.BlockSpec((c_tile, n, common.LANE), lambda c, q, b: (c, 0, b)),
            pl.BlockSpec((c_tile, n, q_tile), lambda c, q, b: (c, 0, q)),
        ],
        out_specs=[out_spec] * n_out,
        scratch_shapes=[pltpu.VMEM((n, q_tile, common.LANE), jnp.int32)],
        name="rnl_fire_times_layer",
        interpret=common.use_interpret(),
    )(t_hi, jnp.swapaxes(times_p, 1, 2), jnp.swapaxes(weights_p, 1, 2))
    outs = [jnp.swapaxes(o[:csz, :qsz, :bsz], 1, 2) for o in outs]
    return tuple(outs) if with_clip else outs[0]


@functools.partial(jax.jit,
                   static_argnames=("t_steps", "threshold", "k"))
def rnl_fire_times_compact(times: jax.Array, weights: jax.Array, *,
                           t_steps: int, threshold: int,
                           k: int | None = None):
    """Fire times over spike-compacted volleys (DESIGN.md §3.3).

    The sparse fast path: volleys have been relocated so each row's active
    lines occupy a dense prefix of width ``s`` (``NO_SPIKE`` padding past
    the prefix), and weights were gathered through the same line-index map
    — per volley, so the weight operand is 3-D. The tick sweep then runs
    over the compacted width ``s`` (instead of ``n``) and stops at the
    batch's last breakpoint tick. Bit-exact vs :func:`rnl_fire_times` on
    the uncompacted inputs because dropped lines carry no ramp bits.

    Args:
      times:   (B, s) int32 compacted spike times
        (:func:`repro.core.compaction.compact_volleys`).
      weights: (B, Q, s) int32 per-volley gathered weights
        (:func:`repro.core.compaction.gather_weights`).
      t_steps, threshold, k: as in :func:`rnl_fire_times`.

    Returns:
      (B, Q) int32 fire times.
    """
    bsz, s = times.shape
    b2, qsz, s2 = weights.shape
    assert bsz == b2 and s == s2, (times.shape, weights.shape)
    b_pad = common.round_up(bsz, B_TILE)
    q_pad, q_tile = _q_tiling(qsz)
    times_p = jnp.pad(times, ((0, b_pad - bsz), (0, 0)),
                      constant_values=int(NO_SPIKE))
    weights_p = jnp.pad(weights, ((0, b_pad - bsz), (0, q_pad - qsz),
                                  (0, 0)))
    t_hi = _sweep_bound(
        jnp.where(times_p[:, None, :] < t_steps,
                  times_p[:, None, :] + weights_p, 0), t_steps, threshold)

    grid = (b_pad // B_TILE, q_pad // q_tile)
    in_specs = [
        _smem_scalar_spec(),
        pl.BlockSpec((B_TILE, s), lambda b, q: (b, 0)),
        pl.BlockSpec((B_TILE, q_tile, s), lambda b, q: (b, q, 0)),
    ]
    out_spec = pl.BlockSpec((B_TILE, q_tile), lambda b, q: (b, q))
    out = pl.pallas_call(
        functools.partial(_rnl_compact_kernel, threshold=threshold, k=k),
        out_shape=jax.ShapeDtypeStruct((b_pad, q_pad), jnp.int32),
        grid=grid, in_specs=in_specs, out_specs=out_spec,
        name="rnl_fire_times_compact",
        interpret=common.use_interpret(),
    )(t_hi, times_p, weights_p)
    return out[:bsz, :qsz]
