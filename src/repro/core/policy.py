"""Engine resolution: the one place that decides which engine runs.

``resolve_engine(backend, column_counts)`` turns a backend name into the
neuron-bank engine that will run (DESIGN.md §3.7):

  * an explicit name (``scan``, ``closed_form``, ``event``, ``pallas``,
    ``pallas_compact``) runs as named — except that a Pallas engine under a
    mesh its column stack cannot tile raises, never switching engines
    behind the caller's back;
  * ``auto`` is the fused Pallas kernel on a TPU wherever it can run, and
    the vectorized closed form everywhere else.

Every input is fixed once the caller knows its platform, mesh and column
counts, so callers resolve once (``TNNEngine`` at construction,
``fire_times_bank`` at trace time). There is no measured-activity input:
no benchmark cell sits on either side of a sparse-versus-dense choice, so
nothing could calibrate one.
"""

from __future__ import annotations

import typing
from typing import Iterable, Literal, Union

import jax

from repro.sharding import compat
from repro.sharding import specs as sharding_specs

Backend = Literal["auto", "scan", "closed_form", "event", "pallas",
                  "pallas_compact"]

#: Engines that evaluate over spike-compacted volleys and therefore take a
#: compaction width (``n_active_max``).
SPARSE_ENGINES = ("event", "pallas_compact")

#: Engines that launch the Pallas kernels (mesh capability applies).
PALLAS_ENGINES = ("pallas", "pallas_compact")

ColumnCounts = Union[int, Iterable[int], None]


def mesh_active() -> bool:
    """Whether an ambient device mesh is entered (compat.set_mesh).

    Under an active mesh engine resolution runs the per-kernel capability
    check (:func:`_pallas_can_run`): Pallas engines whose column stack
    tiles the mesh's ``column`` axis run through the shard_map wrappers
    (:mod:`repro.kernels.rnl_shard`); the rest are refused (explicit) or
    replaced by the bit-exact closed form (``auto``), which is
    sharding-transparent and keeps the layout the layer constraints pin
    (DESIGN.md §6.4).
    """
    return bool(compat.get_abstract_mesh().axis_names)


def _pallas_can_run(column_counts: ColumnCounts) -> bool:
    """Per-kernel mesh capability of the Pallas engines (DESIGN.md §6.4).

    True when no mesh is active (plain single-device launch). Under a
    mesh, the shard_map fast path needs 3-D column stacks whose column
    counts all tile the mesh's ``column`` axis. ``column_counts`` is one
    count (a single bank call), an iterable of per-layer counts (a whole
    network), or ``None`` for a 2-D ``(B, n)`` bank or an unknown shape,
    which only a mesh-free launch can serve; a mesh without a ``column``
    axis has nothing to map over.
    """
    if not mesh_active():
        return True
    if column_counts is None or sharding_specs.TNN_COLUMN_AXIS not in \
            compat.get_abstract_mesh().axis_names:
        return False
    counts = ((column_counts,) if isinstance(column_counts, int)
              else tuple(column_counts))
    size = sharding_specs.tnn_column_size()
    return bool(counts) and all(c % size == 0 for c in counts)


def resolve_engine(backend: Backend,
                   column_counts: ColumnCounts = None) -> str:
    """The engine that runs for ``backend`` on this platform and mesh.

    Raises ValueError for an unknown name, and when an explicit Pallas
    backend cannot run under the ambient mesh (a column count that does
    not tile its ``column`` axis, a 2-D bank, or an unknown shape): only
    ``auto`` may fall back to a jnp engine.
    """
    if backend not in typing.get_args(Backend):
        raise ValueError(f"unknown backend {backend!r}: expected one of "
                         f"{typing.get_args(Backend)}")
    if backend == "auto":
        if jax.default_backend() == "tpu" and _pallas_can_run(column_counts):
            return "pallas"
        return "closed_form"
    if backend in PALLAS_ENGINES and not _pallas_can_run(column_counts):
        raise ValueError(
            f"backend={backend!r} cannot run under the active mesh for "
            f"column counts {column_counts!r} (the shard_map kernels "
            f"need 3-D column stacks that tile the "
            f"{sharding_specs.TNN_COLUMN_AXIS!r} axis, size "
            f"{sharding_specs.tnn_column_size()}); use backend='auto' "
            f"to allow the jnp fallback")
    return backend
