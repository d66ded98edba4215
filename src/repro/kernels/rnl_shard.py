"""shard_map wrappers for the RNL Pallas kernels (DESIGN.md §6.4).

PR 4 sharded the TNN's (columns, neurons) plane over a ``("data",
"column")`` mesh but degraded every Pallas engine to the jnp engines while
a mesh was active — the fastest per-device kernels and the scaled
deployment were mutually exclusive. This module closes that gap the way
the TNN SPU literature scales the silicon: tile columns across units. Each
entry point wraps the existing single-device kernel in ``shard_map`` over
the ``column`` axis (batch stays data-parallel), so every shard runs the
unmodified fused tick sweep on its local ``(C_local, B_local, ...)`` block
— no cross-shard communication exists because columns are independent by
construction, and the per-launch early-exit bound tightens to each shard's
own last breakpoint.

Preconditions (enforced by :func:`repro.core.neuron.pallas_shardable`
before dispatch, re-checked here):

  * an ambient mesh with a ``column`` axis is active (``compat.set_mesh``);
  * the column count divides the axis size (non-dividing counts keep the
    PR 4 replication fallback: the jnp engines).

The batch dim follows ``specs.ambient_fit``: it shards over the DP group
when divisible and silently replicates otherwise — exactly the layout the
``maybe_wsc`` constraints upstream pin, so entering the shard_map never
forces a resharding collective.

On CPU (tests, CI's forced-host-device mesh) the inner ``pallas_call``
runs the interpreter (``kernels.common.use_interpret``, overridable via
``REPRO_PALLAS_INTERPRET``); on TPU the same wrapper lowers each shard to
Mosaic.
"""

from __future__ import annotations

from jax.sharding import PartitionSpec as P

from repro.kernels import rnl_neuron
from repro.sharding import compat
from repro.sharding import specs as sharding_specs


def _mesh_specs(n_columns: int, batch: int):
    """(mesh, column-axis entry, batch-axis entry) for a column-stacked
    launch, or raise if the shard_map path cannot serve this shape."""
    am = compat.get_abstract_mesh()
    if not am.axis_names:
        raise ValueError(
            "no active mesh — call the plain kernels in rnl_neuron")
    col = sharding_specs.TNN_COLUMN_AXIS
    if col not in am.axis_names:
        raise ValueError(
            f"active mesh {am.axis_names} has no {col!r} axis; the TNN "
            "fast path shards columns (sharding.specs.tnn_mesh)")
    if n_columns % int(am.shape[col]):
        raise ValueError(
            f"{n_columns} columns do not divide the {col!r} axis "
            f"(size {int(am.shape[col])}); use the jnp replication "
            "fallback (neuron.pallas_shardable gates dispatch)")
    dp = sharding_specs.ambient_fit(batch, sharding_specs.dp_spec_names())
    return am, col, dp


def rnl_fire_times_layer_sharded(times, weights, *, t_steps: int,
                                 threshold: int, k: int | None = None):
    """:func:`repro.kernels.rnl_neuron.rnl_fire_times_layer` shard_mapped
    over the ``column`` (and data) axes of the ambient mesh.

    Args:
      times:   (C, B, n) int32 per-column spike times, laid out per
        ``specs.tnn_volley_axes`` (columns over ``column``, batch over DP).
      weights: (C, Q, n) int32 per-column weights (columns over ``column``).

    Returns:
      (C, B, Q) int32 fire times, same layout as the fire-times constraint
      in ``layer_forward``. Bit-exact vs the unsharded kernel: shards hold
      whole columns and whole volleys, and the tick sweep is per-(volley,
      neuron) local.
    """
    csz, bsz, _ = times.shape
    mesh, col, dp = _mesh_specs(csz, bsz)

    def local(t, w):
        return rnl_neuron.rnl_fire_times_layer(
            t, w, t_steps=t_steps, threshold=threshold, k=k)

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(col, dp, None), P(col, None, None)),
        out_specs=P(col, dp, None))(times, weights)


def rnl_fire_times_compact_sharded(times, weights, *, t_steps: int,
                                   threshold: int, k: int | None = None):
    """Spike-compacted sharded fast path: per-shard column-fold +
    :func:`repro.kernels.rnl_neuron.rnl_fire_times_compact`.

    Compaction itself (stable-argsort relocation + per-volley weight
    gather, :mod:`repro.core.compaction`) happens *upstream* on the
    sharded tensors — its ops are row-local along the line axis, so it is
    sharding-transparent. This wrapper receives the compacted stack and
    folds each shard's local columns into its batch (the same fold the
    single-device path does globally), so one compact launch per shard
    serves all of its columns.

    Args:
      times:   (C, B, s) int32 compacted spike times.
      weights: (C, B, Q, s) int32 per-volley gathered weights.

    Returns:
      (C, B, Q) int32 fire times.
    """
    csz, bsz, s = times.shape
    qsz = weights.shape[2]
    mesh, col, dp = _mesh_specs(csz, bsz)

    def local(t, w):
        c_l, b_l = t.shape[0], t.shape[1]
        fire = rnl_neuron.rnl_fire_times_compact(
            t.reshape(c_l * b_l, s), w.reshape(c_l * b_l, qsz, s),
            t_steps=t_steps, threshold=threshold, k=k)
        return fire.reshape(c_l, b_l, qsz)

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(col, dp, None), P(col, dp, None, None)),
        out_specs=P(col, dp, None))(times, weights)
