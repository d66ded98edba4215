"""Sharding rules: parameter/activation PartitionSpecs for the production
meshes.

Axes (launch/mesh.py): ``data`` (+ outer ``pod`` on the multi-pod mesh) is
the data-parallel dimension; ``model`` carries tensor/expert parallelism:

  * attention projections: heads over ``model`` (TP)
  * MLP in/out: d_ff over ``model`` (TP)
  * MoE experts: E over ``model`` (EP) and expert d_ff over the DP axes
    (FSDP-style, ZeRO-3) — arctic-480b would not fit per-device otherwise
  * embeddings: vocab over ``model``
  * SSM in/out projections: d_inner over ``model``
  * norms / biases / routers: replicated

Every rule degrades to replication when a dimension is not divisible by
the axis size (e.g. glm4's 2 KV heads on a 16-way model axis) — the
fallback keeps all 40 dry-run cells compiling with the same rule set.
Stacked-layer leaves (leading L axis from scan) get a leading ``None``.
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding import compat

Axis = Union[str, Tuple[str, ...], None]


def axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return int(np.prod([mesh.shape[a] for a in axis]))


def _fit(mesh: Mesh, dim: int, axis: Axis) -> Axis:
    """Use ``axis`` if it divides ``dim``, else try prefixes, else None."""
    if axis is None:
        return None
    if isinstance(axis, str):
        return axis if dim % axis_size(mesh, axis) == 0 else None
    for cut in range(len(axis), 0, -1):
        cand = axis[:cut] if cut > 1 else axis[0]
        if dim % axis_size(mesh, cand) == 0:
            return cand
    return None


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in dp_spec_names() if a in names)


def _leaf_name(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


#: parameter-name -> (spec builder). Specs are for the UNSTACKED leaf; a
#: leading None is prepended for scan-stacked layers.
def _param_spec_base(name: str, shape: Tuple[int, ...], mesh: Mesh,
                     replicate_embed: bool = False) -> P:
    dp = dp_axes(mesh)
    last = name.rsplit("/", 1)[-1]

    def col(d_out_idx=-1):
        """Column-parallel: shard output dim over model."""
        ax = _fit(mesh, shape[d_out_idx], "model")
        spec = [None] * len(shape)
        spec[d_out_idx] = ax
        return P(*spec)

    def row(d_in_idx=0):
        ax = _fit(mesh, shape[d_in_idx], "model")
        spec = [None] * len(shape)
        spec[d_in_idx] = ax
        return P(*spec)

    if last == "embed":
        if replicate_embed:           # H8: batch_over_model (ZeRO-3) mode
            return P(None, None)
        return P(_fit(mesh, shape[0], "model"), None)
    if last == "lm_head":
        return P(None, None) if replicate_embed else col()
    # --- MoE expert stacks: (E, D, F) / (E, F, D) --------------------
    if "moe" in name and last in ("w_gate", "w_up") and len(shape) == 3:
        return P(_fit(mesh, shape[0], "model"), None,
                 _fit(mesh, shape[2], dp))
    if "moe" in name and last == "w_down" and len(shape) == 3:
        return P(_fit(mesh, shape[0], "model"),
                 _fit(mesh, shape[1], dp), None)
    if last == "router":
        return P(None, None)
    # --- attention / MLP / SSM projections ---------------------------
    if last in ("wq", "wk", "wv", "w_ukv", "w_gate", "w_up", "in_proj"):
        return col()
    if last in ("wo", "w_down", "out_proj"):
        return row()
    if last in ("w_dkv", "w_kr", "patch_proj", "frame_proj", "conv_w"):
        return P(*([None] * len(shape)))
    # norms, dt_bias, a_log, scalars
    return P(*([None] * len(shape)))


_STACK_KEYS = ("layers", "dense_layers", "encoder")


def param_pspec(path, leaf, mesh: Mesh, replicate_embed: bool = False) -> P:
    """Leaf spec; robust to optimizer-state prefixes (state.params /
    state.opt.m / state.opt.v all share the parameter's layout)."""
    name = _leaf_name(path)
    shape = leaf.shape
    segs = name.split("/")
    stacked = any(s in _STACK_KEYS for s in segs[:-1])
    if stacked:
        base = _param_spec_base(name, tuple(shape[1:]), mesh,
                                replicate_embed)
        return P(None, *base)
    return _param_spec_base(name, tuple(shape), mesh, replicate_embed)


def param_shardings(params_shape, mesh: Mesh, replicate_embed: bool = False):
    """NamedSharding tree for a params (shape) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, param_pspec(p, l, mesh,
                                                     replicate_embed)),
        params_shape)


# ------------------------------------------------------------ activations
def batch_pspec(mesh: Mesh, batch: int, extra_dims: int = 1,
                over_model: bool = False) -> P:
    """Shard the leading batch dim over as much DP as divides it;
    ``over_model`` additionally folds the model axis into DP (ZeRO-3
    regime for models without tensor-parallel activations)."""
    axes = dp_axes(mesh) + (("model",) if over_model else ())
    ax = _fit(mesh, batch, axes)
    return P(ax, *([None] * extra_dims))


def data_shardings(mesh: Mesh, batch_shapes, over_model: bool = False) -> dict:
    """batch_shapes: dict name -> jax.ShapeDtypeStruct."""
    out = {}
    for k, v in batch_shapes.items():
        out[k] = NamedSharding(
            mesh, batch_pspec(mesh, v.shape[0], len(v.shape) - 1,
                              over_model))
    return out


def param_pspecs(params_shape, mesh: Mesh, replicate_embed: bool = False):
    """PartitionSpec tree (for with_sharding_constraint on grads)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: param_pspec(p, l, mesh, replicate_embed), params_shape)


def cache_pspec(path, leaf, mesh: Mesh) -> P:
    """Serve-state sharding: batch over DP; KV heads over model when they
    divide; SSD state heads over model."""
    name = _leaf_name(path)
    shape = leaf.shape
    if name.endswith("pos") or leaf.ndim == 0:
        return P()
    stacked = name.startswith("layer_caches")
    body = tuple(shape[1:]) if stacked else tuple(shape)
    dp = dp_axes(mesh)
    spec: list = [None] * len(body)
    if len(body) >= 1:
        spec[0] = _fit(mesh, body[0], dp)            # batch dim
    if len(body) == 4:                               # (B,S,Hkv,D) | (B,H,N,P)
        spec[2] = _fit(mesh, body[2], "model") if name.endswith(
            ("/k", "/v")) else spec[2]
        if "state" in name:
            spec[1] = _fit(mesh, body[1], "model")   # SSD heads
    if stacked:
        spec = [None] + spec
    return P(*spec)


def serve_shardings(state_shape, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, cache_pspec(p, l, mesh)),
        state_shape)


# ------------------------------------------------- in-model constraints
def ambient_fit(dim: int, entry: Axis) -> Axis:
    """Resolve one axis entry against the AMBIENT mesh (``compat.set_mesh``
    scope): the subset of ``entry``'s axes the mesh actually has, when
    their combined size divides ``dim`` — else None (replication). This is
    the single per-dim rule shared by the in-jit constraints
    (:func:`maybe_wsc`) and the shard_map fast path
    (:mod:`repro.kernels.rnl_shard`), so the two can never disagree about
    a tensor's layout."""
    am = compat.get_abstract_mesh()
    if not am.axis_names or entry is None:
        return None
    names = set(am.axis_names)
    entry_t = entry if isinstance(entry, tuple) else (entry,)
    avail = tuple(a for a in entry_t if a in names)
    if not avail:
        return None
    size = int(np.prod([am.shape[a] for a in avail]))
    if dim % size:
        return None
    return avail if len(avail) > 1 else avail[0]


def maybe_wsc(x, *spec):
    """with_sharding_constraint that degrades to identity when the named
    axes are absent (CPU unit tests, single-device benches). ``spec``
    entries are axis names, tuples of axis names, or None; axes that do
    not divide the corresponding dim are dropped (:func:`ambient_fit`).

    Under jit the constraint binds to the ambient abstract mesh. An eager
    (concrete) array is placed on the concrete mesh ``set_mesh`` entered:
    it may still sit on one device, and a bare PartitionSpec cannot
    reshard a single-device array."""
    if not compat.get_abstract_mesh().axis_names:
        return x
    resolved = P(*(ambient_fit(d, e) for d, e in zip(x.shape, spec)))
    if compat.is_tracer(x):
        return jax.lax.with_sharding_constraint(x, resolved)
    return jax.device_put(x, NamedSharding(compat.get_mesh(), resolved))


def dp_spec_names() -> tuple:
    """The DP axis group for in-model constraints."""
    return ("pod", "data")


# ------------------------------------------------------------ TNN rules
# The TNN stack scales by tiling RNL columns side by side (the paper's
# silicon replicates column hardware across the die); the software
# analogue shards the (columns, neurons) plane over a ``column`` mesh
# axis and the volley batch over ``data`` (DESIGN.md §6.4):
#
#   tensor                      shape         spec
#   ------------------------    -----------   --------------------------
#   layer weights               (C, Q, rf)    P(column, None, None)
#   post-gather volleys         (C, B, rf)    P(column, data, None)
#   bank fire times             (C, B, Q)     P(column, data, None)
#   post-WTA / winners          (B, C, ...)   P(data, column, ...)
#   input volley batch          (B, n_in)     P(data, None)
#
# Every rule runs through ``_fit``: a column count (or batch) that the
# axis does not divide degrades that dim to replication, so the same
# rule set compiles unchanged on CPU / single-device (no mesh: the
# in-model constraints are identity via ``maybe_wsc``).

#: mesh axis carrying the (columns, neurons) plane
TNN_COLUMN_AXIS = "column"


def tnn_column_size() -> int:
    """Size of the ambient mesh's ``column`` axis (1 when no mesh is
    active or the mesh has no such axis). The divisor a column count must
    divide for the shard_map Pallas fast path to tile it
    (:func:`repro.core.policy.resolve_engine`)."""
    am = compat.get_abstract_mesh()
    if TNN_COLUMN_AXIS not in am.axis_names:
        return 1
    return int(am.shape[TNN_COLUMN_AXIS])


def tnn_mesh(n_column: int | None = None, n_data: int = 1, *,
             devices=None) -> Mesh:
    """Mesh with ``("data", "column")`` axes over the local devices.

    ``n_column`` defaults to all devices not consumed by ``n_data``; a
    1x1 mesh (single device) is valid and makes every rule replicate.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if n_data <= 0:
        raise ValueError(f"n_data must be positive, got {n_data}")
    if n_column is None:
        if len(devices) % n_data:
            raise ValueError(
                f"n_data={n_data} does not divide {len(devices)} devices")
        n_column = len(devices) // n_data
    if n_column <= 0:
        raise ValueError(f"n_column must be positive, got {n_column}")
    need = n_data * n_column
    if need > len(devices):
        raise ValueError(f"mesh ({n_data}, {n_column}) needs {need} "
                         f"devices, have {len(devices)}")
    dev = np.asarray(devices[:need]).reshape(n_data, n_column)
    return Mesh(dev, ("data", TNN_COLUMN_AXIS))


def tnn_param_pspec(mesh: Mesh, n_columns: int) -> P:
    """Layer weights (C, Q, rf): columns over ``column``, else replicate."""
    return P(_fit(mesh, n_columns, TNN_COLUMN_AXIS), None, None)


def tnn_param_axes() -> tuple:
    """``maybe_wsc`` axis entries for a (C, Q, rf) weight stack — the
    in-jit twin of :func:`tnn_param_pspec` (same rule, ``ambient_fit``
    fallback per dim). The STDP update path (``layer_step``) pins its new
    weights with this, so a learning step's output params land exactly
    where :func:`tnn_param_pspec` placed the input params and a
    learn-while-serving engine never reshards weights between steps."""
    return (TNN_COLUMN_AXIS, None, None)


def tnn_volley_axes() -> tuple:
    """``maybe_wsc`` axis entries for column-stacked volley tensors
    ``(C, B, ...)`` — the single encoding of the post-gather rule; the
    in-layer/in-bank constraints and :func:`tnn_data_pspec` both derive
    from it, so the rule cannot drift between the two."""
    return (TNN_COLUMN_AXIS, dp_spec_names(), None)


def tnn_data_pspec(mesh: Mesh, n_columns: int, batch: int) -> P:
    """Post-gather volley tensor (C, B, rf): columns over ``column``,
    batch over the DP group; either dim degrades independently. For
    callers that materialize the receptive-field gather *outside* jit and
    place it themselves — the in-jit path constrains the same tensor via
    ``maybe_wsc(*tnn_volley_axes())``, which this derives from."""
    col, _, _ = tnn_volley_axes()
    return P(_fit(mesh, n_columns, col),
             _fit(mesh, batch, dp_axes(mesh)), None)


def tnn_batch_pspec(mesh: Mesh, batch: int) -> P:
    """Input volley batch (B, n_inputs): batch over the DP group."""
    return batch_pspec(mesh, batch, extra_dims=1)


def tnn_stage_axes() -> tuple:
    """``maybe_wsc`` axis entries for a gamma-cycle pipeline stage buffer
    ``(mb, n_lines)`` (DESIGN.md §6.5): the micro-batch over the DP group
    and the flattened ``C_l * Q_l`` output lines over ``column`` — so a
    stage's lines live on the column shards of the layer that produced
    them, and the next layer's receptive-field gather reads locally."""
    return (dp_spec_names(), TNN_COLUMN_AXIS)


def tnn_stage_pspec(mesh: Mesh, batch: int, n_lines: int) -> P:
    """Stage-to-shard placement for a pipeline stage buffer ``(mb,
    n_lines)`` — the externally-placed twin of
    :func:`tnn_stage_axes` (same rule, ``_fit`` fallback per dim)."""
    dp, col = tnn_stage_axes()
    return P(_fit(mesh, batch, dp_axes(mesh)),
             _fit(mesh, n_lines, col))


def tnn_carry_axes() -> tuple:
    """``maybe_wsc`` axis entries for a recurrent carry ``(B, n_outputs)``
    (DESIGN.md §6.5): batch over the DP group, the flattened ``C * Q``
    previous-cycle output lines over ``column``. Deliberately the same
    rule as a pipeline stage buffer — a carry IS last cycle's output
    volley, so its lines already live on the column shards of the layer
    that produced (and will re-consume) them; threading state across
    gamma cycles moves no data between shards."""
    return tnn_stage_axes()


def tnn_carry_pspec(mesh: Mesh, batch: int, n_outputs: int) -> P:
    """Host-to-shard placement for a recurrent carry ``(B, n_outputs)`` —
    the externally-placed twin of :func:`tnn_carry_axes` (same rule,
    ``_fit`` fallback per dim); what the serve engine uses to place each
    slot's carry rows next to the layer weights that consume them."""
    return tnn_stage_pspec(mesh, batch, n_outputs)
