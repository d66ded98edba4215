"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS for 512 host devices before any jax
import; tests and benches see the real single device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis in Auto mode: the sharding rules
    (sharding/specs.py) place arrays with NamedShardings and
    ``with_sharding_constraint`` and let the SPMD partitioner infer the
    rest. jax's default Explicit axes would instead demand an
    ``out_sharding`` on every gather and reshape of a sharded operand."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; (2,16,16) = 512 chips across 2 pods.

    Axes: ``pod`` (outer DP, crosses the slow inter-pod links), ``data``
    (intra-pod DP / FSDP), ``model`` (TP/EP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_dev_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh for CPU integration tests (requires that many host
    devices; see tests/conftest notes)."""
    return _auto_mesh((data, model), ("data", "model"))
