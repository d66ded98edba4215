"""Sharding-rule unit tests: pure PartitionSpec logic (no devices needed —
a 1x1 mesh exercises the rule structure; divisibility fallbacks are
checked against a mocked mesh shape)."""

import jax
from jax.sharding import PartitionSpec as P

from repro.sharding import specs as SH


class FakeMesh:
    """Just enough Mesh interface for the rule functions."""

    def __init__(self, shape_map):
        self.shape = dict(shape_map)
        self.axis_names = tuple(shape_map)


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


class Leaf:
    def __init__(self, *shape):
        self.shape = tuple(shape)
        self.ndim = len(shape)


def _spec(name, *shape, mesh=MESH, **kw):
    path = tuple(jax.tree_util.GetAttrKey(p) for p in name.split("/"))
    return SH.param_pspec(path, Leaf(*shape), mesh, **kw)


def test_attention_projections_tp():
    assert _spec("params/layers/attn/wq", 40, 4096, 4096) == \
        P(None, None, "model")
    assert _spec("params/layers/attn/wo", 40, 4096, 4096) == \
        P(None, "model", None)


def test_kv_heads_fallback_to_replication():
    # glm4: 2 KV heads * 128 = 256 cols -> divisible; but 2 heads alone
    # would not be. A 24-col projection is NOT divisible by 16 -> None.
    assert _spec("params/layers/attn/wk", 40, 4096, 24) == P(None, None, None)


def test_moe_expert_ep_plus_fsdp():
    s = _spec("params/layers/moe/w_gate", 35, 128, 7168, 4864)
    assert s == P(None, "model", None, "data")
    s = _spec("params/layers/moe/w_down", 35, 128, 4864, 7168)
    assert s == P(None, "model", "data", None)


def test_moe_fsdp_spans_pod_axis():
    s = _spec("params/layers/moe/w_gate", 35, 128, 7168, 4864, mesh=MESH3)
    assert s == P(None, "model", None, ("pod", "data"))


def test_optimizer_state_inherits_param_layout():
    a = _spec("params/layers/mlp/w_gate", 24, 2048, 8192)
    b = _spec("opt/m/layers/mlp/w_gate", 24, 2048, 8192)
    assert a == b == P(None, None, "model")


def test_embed_rules():
    assert _spec("params/embed", 151552, 4096) == P("model", None)
    assert _spec("params/embed", 151552, 4096, replicate_embed=True) == \
        P(None, None)
    # odd vocab not divisible by 16 -> replicated
    assert _spec("params/embed", 92545, 4096) == P(None, None)


def test_norms_replicated():
    assert _spec("params/layers/norm1", 24, 4096) == P(None, None)
    assert _spec("params/final_norm", 4096) == P(None)


def test_batch_pspec_fallbacks():
    assert SH.batch_pspec(MESH3, 256, 1) == P(("pod", "data"), None)
    assert SH.batch_pspec(MESH3, 1, 1) == P(None, None)     # long_500k
    assert SH.batch_pspec(MESH, 256, 1, over_model=True) == \
        P(("data", "model"), None)
    # 256 not divisible by 512 -> falls back to (pod, data)
    assert SH.batch_pspec(MESH3, 256, 1, over_model=True) == \
        P(("pod", "data"), None)


def test_fit_prefix_fallback():
    assert SH._fit(MESH3, 32, ("pod", "data", "model")) == ("pod", "data")
    assert SH._fit(MESH3, 2, ("pod", "data")) == "pod"
    assert SH._fit(MESH3, 3, ("pod", "data")) is None


TNN_MESH = FakeMesh({"data": 2, "column": 4})


def test_tnn_param_pspec_column_axis():
    # C=8 divides the 4-way column axis -> sharded; C=5 -> replicated
    assert SH.tnn_param_pspec(TNN_MESH, 8) == P("column", None, None)
    assert SH.tnn_param_pspec(TNN_MESH, 5) == P(None, None, None)


def test_tnn_data_pspec_independent_fallbacks():
    # (C, B, rf): each dim degrades to replication independently
    assert SH.tnn_data_pspec(TNN_MESH, 8, 6) == P("column", "data", None)
    assert SH.tnn_data_pspec(TNN_MESH, 5, 6) == P(None, "data", None)
    assert SH.tnn_data_pspec(TNN_MESH, 8, 3) == P("column", None, None)
    assert SH.tnn_data_pspec(TNN_MESH, 5, 3) == P(None, None, None)


def test_tnn_batch_pspec_over_data():
    assert SH.tnn_batch_pspec(TNN_MESH, 6) == P("data", None)
    assert SH.tnn_batch_pspec(TNN_MESH, 3) == P(None, None)
    # a pod axis folds into the DP group like the LM rules
    mesh3 = FakeMesh({"pod": 2, "data": 2, "column": 4})
    assert SH.tnn_batch_pspec(mesh3, 8) == P(("pod", "data"), None)


def test_tnn_stage_pspec_lines_over_column():
    # pipeline stage buffer (mb, C_l*Q_l): micro-batch over DP, output
    # lines over column; each dim degrades independently (DESIGN.md §6.5)
    assert SH.tnn_stage_pspec(TNN_MESH, 4, 8) == P("data", "column")
    assert SH.tnn_stage_pspec(TNN_MESH, 3, 8) == P(None, "column")
    assert SH.tnn_stage_pspec(TNN_MESH, 4, 6) == P("data", None)
    assert SH.tnn_stage_pspec(TNN_MESH, 3, 6) == P(None, None)
    # the in-jit encoding and the placed spec derive from the same rule
    dp, col = SH.tnn_stage_axes()
    assert col == SH.TNN_COLUMN_AXIS and dp == SH.dp_spec_names()


def test_cache_pspec_kv_heads():
    path = (jax.tree_util.GetAttrKey("layer_caches"),
            jax.tree_util.GetAttrKey("k"))
    s = SH.cache_pspec(path, Leaf(40, 128, 32768, 16, 128), MESH)
    assert s == P(None, "data", None, "model", None)
    # 2 KV heads don't divide 16 -> replicated head axis
    s = SH.cache_pspec(path, Leaf(40, 128, 32768, 2, 128), MESH)
    assert s == P(None, "data", None, None, None)


def test_ambient_fit_resolution(monkeypatch):
    """ambient_fit against a mocked ambient mesh: axis kept when it
    divides the dim, dropped to replication otherwise, tuple entries
    filtered to the axes the mesh has."""
    from repro.sharding import compat

    monkeypatch.setattr(compat, "get_abstract_mesh",
                        lambda: FakeMesh({"data": 2, "column": 4}))
    assert SH.ambient_fit(8, "column") == "column"
    assert SH.ambient_fit(5, "column") is None       # 5 % 4 -> replication
    assert SH.ambient_fit(6, None) is None
    assert SH.ambient_fit(8, ("pod", "data")) == "data"  # mesh has no pod
    assert SH.ambient_fit(8, ("data", "column")) == ("data", "column")
    monkeypatch.setattr(compat, "get_abstract_mesh", lambda: FakeMesh({}))
    assert SH.ambient_fit(8, "column") is None       # no mesh -> identity


def test_maybe_wsc_resolves_dims_in_order(monkeypatch):
    """Regression: maybe_wsc must pair x.shape[i] with spec[i] when
    resolving each dim. A swapped zip binds the int dim as the axis
    entry, which silently resolves EVERY constraint to full replication
    (values stay bit-exact, so only a spec-level assertion catches it)."""
    from repro.sharding import compat

    monkeypatch.setattr(compat, "get_abstract_mesh",
                        lambda: FakeMesh({"data": 2, "column": 4}))
    captured = {}

    def fake_wsc(x, spec):
        captured["spec"] = spec
        return x

    monkeypatch.setattr(jax.lax, "with_sharding_constraint", fake_wsc)
    # the Leaf stands in for a traced value (the in-jit constraint path)
    monkeypatch.setattr(compat, "is_tracer", lambda x: True)
    x = Leaf(8, 6, 7)
    assert SH.maybe_wsc(x, "column", "data", None) is x
    assert captured["spec"] == P("column", "data", None)
    # non-dividing dims degrade individually, order preserved
    SH.maybe_wsc(Leaf(5, 6, 7), "column", "data", None)
    assert captured["spec"] == P(None, "data", None)
