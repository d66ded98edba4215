"""Sharded Pallas fast path (DESIGN.md §6.4): shard_map-wrapped
``pallas`` / ``pallas_compact`` vs the jnp engines on the 2x4 host mesh.

Property-style equivalence suite for kernels/rnl_shard + the per-kernel
capability model in core/neuron: random sparse draws, all-silent and
fully-dense batches, the ragged C=5 replication fallback, lane-bucket
boundary widths, and the §5.4 pipelined composition — all bit-exact
against single-device ``scan`` / ``event`` references.

Same subprocess isolation contract as tests/test_sharding_tnn.py (the
main pytest process must keep seeing one device); additionally each
subprocess forces ``REPRO_PALLAS_INTERPRET=1`` so the Pallas interpreter
is exercised *explicitly* through the override (not backend sniffing) —
the same lane CI's shard-tests job runs.
"""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.kernels import common

REPO = pathlib.Path(__file__).resolve().parents[1]

#: shared preamble — mirrors tests/test_sharding_tnn.py: a 2-layer
#: network with mesh-dividing columns (8 -> 4 on the 4-way column axis),
#: a non-dividing C=5 net (replication fallback), and the (data=2,
#: column=4) host mesh.
SETUP = """
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.core import coding, compaction, layer, network, neuron, policy
    from repro.sharding import compat
    from repro.sharding import specs as SH

    assert jax.device_count() == 8, jax.devices()
    NS = int(coding.NO_SPIKE)

    def sparse_volleys(rng, bsz, n, t_max=20, t_steps=12):
        t = rng.integers(0, t_max, size=(bsz, n))
        return np.where(t >= t_steps, NS, t).astype(np.int32)

    l1 = layer.TNNLayer(n_columns=8, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=12, dendrite="catwalk", k=2)
    l2 = layer.TNNLayer(n_columns=4, rf_size=6, n_neurons=4, threshold=4,
                        t_steps=12, dendrite="catwalk", k=2)
    net = network.make_network([l1, l2])
    odd = network.make_network([dataclasses.replace(l1, n_columns=5)])
    params = network.init_network(jax.random.PRNGKey(0), net)
    podd = network.init_network(jax.random.PRNGKey(1), odd)
    rng = np.random.default_rng(0)
    v = sparse_volleys(rng, 8, net.n_inputs)
    vodd = sparse_volleys(rng, 8, odd.n_inputs)
    mesh = SH.tnn_mesh(4, 2)                       # (data=2, column=4)
"""


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    env["REPRO_PALLAS_INTERPRET"] = "1"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SETUP) + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_use_interpret_explicit_override(monkeypatch):
    """REPRO_PALLAS_INTERPRET=0/1 beats backend sniffing (and the legacy
    REPRO_KERNEL_INTERPRET alias still works) — no subprocess needed now
    that the selector is uncached."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    assert common.use_interpret() == (common.jax.default_backend() == "cpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert common.use_interpret() is False      # force-compile, even on CPU
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert common.use_interpret() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    assert common.use_interpret() is False      # legacy alias honored
    # the new name wins when both are set
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert common.use_interpret() is True
    # strict parse: the old truthy-ing accepted "true"/"false" and
    # silently INVERTED "false"; now anything but 0/1 raises
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "true")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        common.use_interpret()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "false")
    with pytest.raises(ValueError, match="expected '0' or '1'"):
        common.use_interpret()
    # empty string == unset (the shell's way of clearing a knob)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "")
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    assert common.use_interpret() is True


def test_sharded_pallas_network_bit_exact_property():
    """network.forward with pallas/pallas_compact layers on the (2, 4)
    mesh == the single-device scan reference, over random sparse draws
    plus the all-silent and fully-dense edges; on the ragged C=5 net an
    explicit Pallas layer raises, and ``auto`` (on a TPU backend) takes the
    replication fallback and must agree too."""
    print(_run("""
        for backend in ("pallas", "pallas_compact", "auto"):
            for cfg0, ps in ((net, params), (odd, podd)):
                bnet = network.make_network(
                    [dataclasses.replace(lc, backend=backend)
                     for lc in cfg0.layers])
                if cfg0 is odd and backend != "auto":
                    with compat.set_mesh(mesh):
                        try:
                            network.forward(ps, vodd, bnet)
                        except ValueError:
                            continue
                    raise AssertionError(f"explicit {backend} with C=5")
                draws = [sparse_volleys(np.random.default_rng(s), 8,
                                        cfg0.n_inputs) for s in range(3)]
                draws.append(np.full((8, cfg0.n_inputs), NS, np.int32))
                draws.append(np.asarray(
                    np.random.default_rng(7).integers(
                        0, 12, size=(8, cfg0.n_inputs)), np.int32))
                snet = network.make_network(
                    [dataclasses.replace(lc, backend="scan")
                     for lc in cfg0.layers])
                sp = jax.device_put(ps, network.param_shardings(bnet, mesh))
                for volleys in draws:
                    rres = network.forward(ps, volleys, snet)
                    ref, ref_win = np.asarray(rres.out), rres.winners
                    jb = jax.default_backend
                    if backend == "auto":   # TPU policy: pallas or fallback
                        jax.default_backend = lambda: 'tpu'
                    try:
                        with compat.set_mesh(mesh):
                            vs = jax.device_put(
                                volleys, network.data_sharding(
                                    bnet, mesh, volleys.shape[0]))
                            sres = network.forward(sp, vs, bnet)
                            out, win = sres.out, sres.winners
                    finally:
                        jax.default_backend = jb
                    np.testing.assert_array_equal(np.asarray(out), ref)
                    for w_ref, w_sh in zip(ref_win, win):
                        np.testing.assert_array_equal(np.asarray(w_sh),
                                                      np.asarray(w_ref))
        print('SHARDED_PALLAS_FWD_BIT_EXACT_OK')
    """))


def test_sharded_kernel_wrappers_and_capability_errors():
    """Direct kernels/rnl_shard coverage: bit-exact vs the unsharded
    kernels on a dividing stack, loud ValueError outside a mesh and on a
    non-dividing column count (the shapes policy.resolve_engine gates
    out before dispatch)."""
    print(_run("""
        from repro.kernels import rnl_neuron, rnl_shard
        cfgn = l1.neuron_config()
        times_rf = jnp.swapaxes(jnp.asarray(v)[:, l1.rf_index()], 0, 1)
        w = jnp.round(params[0]).astype(jnp.int32)
        ref = np.asarray(rnl_neuron.rnl_fire_times_layer(
            times_rf, w, t_steps=12, threshold=5, k=2))
        with compat.set_mesh(mesh):
            got = rnl_shard.rnl_fire_times_layer_sharded(
                times_rf, w, t_steps=12, threshold=5, k=2)
            np.testing.assert_array_equal(np.asarray(got), ref)
            comp = compaction.compact_volleys(times_rf, 12)
            w_c = compaction.gather_weights(w, comp.line_index)
            got_c = rnl_shard.rnl_fire_times_compact_sharded(
                comp.times, w_c, t_steps=12, threshold=5, k=2)
            np.testing.assert_array_equal(np.asarray(got_c), ref)
            try:                                   # C=5 does not divide 4
                rnl_shard.rnl_fire_times_layer_sharded(
                    times_rf[:5], w[:5], t_steps=12, threshold=5, k=2)
            except ValueError:
                pass
            else:
                raise AssertionError('expected ValueError for C=5')
        try:                                       # no mesh entered
            rnl_shard.rnl_fire_times_layer_sharded(
                times_rf, w, t_steps=12, threshold=5, k=2)
        except ValueError:
            pass
        else:
            raise AssertionError('expected ValueError without a mesh')
        print('SHARD_WRAPPER_OK')
    """))


def test_auto_resolves_to_pallas_under_mesh():
    """Acceptance criterion: under the 2x4 mesh with dividing C and a TPU
    backend, ``policy.resolve_engine("auto", ...)`` resolves to a Pallas
    engine and the auto-dispatched bank output is bit-exact vs
    single-device scan (interpret mode stands in for Mosaic on the
    host)."""
    print(_run("""
        cfgn = l1.neuron_config()
        times_rf = jnp.swapaxes(jnp.asarray(v)[:, l1.rf_index()], 0, 1)
        w = jnp.round(params[0]).astype(jnp.int32)
        ref = np.asarray(neuron.fire_times_bank(times_rf, w, cfgn,
                                                backend='scan'))
        with compat.set_mesh(mesh):
            jb, jax.default_backend = jax.default_backend, lambda: 'tpu'
            try:
                assert policy.resolve_engine('auto', 8) == 'pallas'
                assert policy.resolve_engine('auto', (8, 4)) == 'pallas'
                got = neuron.fire_times_bank(times_rf, w, cfgn,
                                             backend='auto')
            finally:
                jax.default_backend = jb
            np.testing.assert_array_equal(np.asarray(got), ref)
        print('AUTO_PALLAS_UNDER_MESH_OK')
    """))


def test_lane_bucket_boundary_widths():
    """pallas_compact at compacted widths straddling the bucket ladder's
    lane boundary (s = 127 / 128 / 129 -> buckets 128 / 128 / 256) stays
    bit-exact vs the event engine through the sharded dispatch."""
    print(_run("""
        lane = compaction.LANE_WIDTH
        big = layer.TNNLayer(n_columns=8, rf_size=160, n_neurons=2,
                             threshold=40, t_steps=16, dendrite="catwalk",
                             k=4)
        cfgn = big.neuron_config()
        wkey = jax.random.PRNGKey(3)
        w = jax.random.randint(wkey, (8, 2, 160), 0, 8, jnp.int32)
        rng = np.random.default_rng(9)
        for s, bucket in ((lane - 1, lane), (lane, lane),
                          (lane + 1, 2 * lane)):
            assert compaction.bucket_width(s) == bucket
            t = np.full((8, 4, 160), NS, np.int32)
            for c in range(8):
                for b in range(4):
                    hot = rng.choice(160, size=s, replace=False)
                    t[c, b, hot] = rng.integers(0, 16, size=s)
            assert compaction.max_active(t, 16) == s
            ref = np.asarray(neuron.fire_times_bank(
                jnp.asarray(t), w, cfgn, backend='event'))
            with compat.set_mesh(mesh):
                got = neuron.fire_times_bank(
                    jnp.asarray(t), w, cfgn, backend='pallas_compact',
                    n_active_max=bucket)
            np.testing.assert_array_equal(np.asarray(got), ref)
        print('LANE_BUCKET_BOUNDARY_OK')
    """))


def test_maybe_wsc_layouts_on_host_mesh():
    """Layout (not value) assertions for the in-jit maybe_wsc
    constraints on the real (data=2, column=4) mesh. Bit-exactness
    alone cannot catch a constraint that silently resolves to full
    replication — the values are identical either way — so this pins
    the resolved PartitionSpecs themselves: the jitted constraint
    output must land on P('column','data'), the ragged C=5 shape must
    degrade only its column dim, and a pallas-backed network.forward
    must keep its outputs tiled over the column axis end to end."""
    print(_run("""
        from jax.sharding import PartitionSpec as P
        x = np.zeros((8, 6, 7), np.float32)
        with compat.set_mesh(mesh):
            f = jax.jit(lambda a: SH.maybe_wsc(a, 'column', 'data', None))
            assert f(x).sharding.spec == P('column', 'data'), \
                f(x).sharding.spec
            assert f(np.zeros((5, 6, 7), np.float32)).sharding.spec == \
                P(None, 'data')                       # 5 % 4 -> repl dim 0
        # no ambient mesh: identity, no constraint introduced
        g = jax.jit(lambda a: SH.maybe_wsc(a, 'column', 'data', None))
        assert 'column' not in str(g(x).sharding)
        # end to end: the pallas shard_map path leaves outputs tiled
        bnet = network.make_network(
            [dataclasses.replace(lc, backend='pallas')
             for lc in net.layers])
        sp = jax.device_put(params, network.param_shardings(bnet, mesh))
        with compat.set_mesh(mesh):
            vs = jax.device_put(v, network.data_sharding(bnet, mesh,
                                                         v.shape[0]))
            fwd = jax.jit(lambda p, x: network.forward(p, x, bnet)[:2])
            out, win = fwd(sp, vs)
        assert out.sharding.spec == P('data', 'column'), out.sharding.spec
        for w in win:
            assert w.sharding.spec == P('data', 'column'), w.sharding.spec
        print('MAYBE_WSC_LAYOUTS_OK')
    """))


def test_sharded_pipelined_pallas_bit_exact():
    """network.forward(..., microbatches=M) composes with the shard_map
    Pallas path:
    the §5.4 schedule over pallas (and width-pinned pallas_compact)
    layers on the (2, 4) mesh matches the single-device barriered scan
    reference for ragged and degenerate micro-batch splits."""
    print(_run("""
        rres = network.forward(params, v, net)
        ref, ref_win = np.asarray(rres.out), rres.winners
        widths = network.sparse_widths(
            net, compaction.bucket_width(
                compaction.max_active(v[:, np.asarray(l1.rf_index())],
                                      l1.t_steps)))
        variants = [
            [dataclasses.replace(lc, backend="pallas")
             for lc in net.layers],
            [dataclasses.replace(lc, backend="pallas_compact",
                                 n_active_max=wd)
             for lc, wd in zip(net.layers, widths)],
        ]
        for layers in variants:
            bnet = network.make_network(layers)
            sp = jax.device_put(params, network.param_shardings(bnet, mesh))
            for m in (1, 3, 8):
                fwd = jax.jit(lambda p, x, n=bnet, m=m:
                              network.forward(p, x, n, microbatches=m)[:2])
                with compat.set_mesh(mesh):
                    vs = jax.device_put(
                        v, network.data_sharding(bnet, mesh, v.shape[0]))
                    out, win = fwd(sp, vs)
                np.testing.assert_array_equal(np.asarray(out), ref)
                for w_sh, w_ref in zip(win, ref_win):
                    np.testing.assert_array_equal(np.asarray(w_sh),
                                                  np.asarray(w_ref))
        print('SHARDED_PIPELINED_PALLAS_OK')
    """))


def test_explicit_pallas_raises_under_nondividing_mesh():
    """An explicit Pallas request that the 4-device column mesh cannot
    tile (C=5, or a 2-D bank) raises — at the bank, the network and the
    serve engine — instead of quietly running a jnp engine."""
    print(_run("""
        from repro.serve import tnn_engine
        mesh4 = SH.tnn_mesh(n_column=4)               # (data=1, column=4)
        assert mesh4.devices.size == 4
        cfgn = l1.neuron_config()
        times_rf = jnp.swapaxes(jnp.asarray(vodd)[:, odd.layers[0].rf_index()],
                                0, 1)                  # (5, B, rf)
        w = jnp.round(podd[0]).astype(jnp.int32)

        def raises(fn):
            try:
                fn()
            except ValueError as e:
                assert "backend='auto'" in str(e), e
            else:
                raise AssertionError('explicit pallas did not raise')

        for backend in ('pallas', 'pallas_compact'):
            bnet = network.make_network(
                [dataclasses.replace(lc, backend=backend)
                 for lc in odd.layers])
            with compat.set_mesh(mesh4):
                raises(lambda: neuron.fire_times_bank(times_rf, w, cfgn,
                                                      backend=backend))
                raises(lambda: neuron.fire_times_bank(times_rf[0], w[0],
                                                      cfgn, backend=backend))
                raises(lambda: network.forward(podd, vodd, bnet))
            raises(lambda: tnn_engine.TNNEngine(
                podd, odd, tnn_engine.TNNServeConfig(n_slots=2,
                                                     backend=backend),
                mesh=mesh4))
        print('EXPLICIT_PALLAS_RAISES_OK')
    """))


def test_auto_degrades_under_nondividing_mesh():
    """``auto`` on a TPU backend under the same 4-device mesh with C=5
    falls back to a jnp engine and stays bit-exact; with dividing
    columns it keeps the Pallas kernel."""
    print(_run("""
        from repro.serve import tnn_engine
        mesh4 = SH.tnn_mesh(n_column=4)
        ref = np.asarray(network.forward(podd, vodd, odd).out)
        jb, jax.default_backend = jax.default_backend, lambda: 'tpu'
        try:
            with compat.set_mesh(mesh4):
                assert policy.resolve_engine('auto', 5) == 'closed_form'
                assert policy.resolve_engine('auto', 8) == 'pallas'
                got = network.forward(podd, vodd, odd).out
            np.testing.assert_array_equal(np.asarray(got), ref)
            eng = tnn_engine.TNNEngine(
                podd, odd, tnn_engine.TNNServeConfig(n_slots=4), mesh=mesh4)
            out = eng.serve([vodd[:3], vodd[3:]])
        finally:
            jax.default_backend = jb
        for s, r in zip([vodd[:3], vodd[3:]], out):
            np.testing.assert_array_equal(
                tnn_engine.reference_outputs(podd, odd, s), r)
        assert eng.engine == 'closed_form', eng.engine
        assert eng.stats()['n_steps'] > 0
        print('AUTO_DEGRADES_OK')
    """))
