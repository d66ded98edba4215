"""The jax mesh/shard_map API in one place.

The distribution layer uses the ambient-mesh API (``jax.set_mesh`` /
``jax.sharding.get_abstract_mesh`` / ``jax.sharding.get_mesh`` /
``jax.shard_map``) and the tracer
check through these wrappers, so a future jax API move is a one-file edit.
"""

from __future__ import annotations

import jax


def is_tracer(x) -> bool:
    """True when ``x`` is a jax tracer (host-side measurement impossible).

    Host-side policy code branches on concreteness through this
    (density measurement, compaction width selection).
    """
    return isinstance(x, jax.core.Tracer)


def get_abstract_mesh():
    """The ambient mesh (entered via :func:`set_mesh`); empty
    (``axis_names == ()``) when none is active."""
    return jax.sharding.get_abstract_mesh()


def get_mesh():
    """The concrete mesh :func:`set_mesh` entered (device placement of
    eager arrays)."""
    return jax.sharding.get_mesh()


def set_mesh(mesh):
    """Context manager activating ``mesh`` for ambient-mesh lookups."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
