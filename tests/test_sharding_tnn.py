"""Sharded TNN path (DESIGN.md §6.4): bit-exactness of the mesh-aware
(columns, neurons) plane vs the single-device path.

Needs >1 device, so each test runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (same isolation
contract as tests/test_distribution.py — the main test process must keep
seeing one device). The CI ``shard-tests`` job runs this module under the
same flag at the job level.
"""

import os
import pathlib
import subprocess
import sys
import textwrap

REPO = pathlib.Path(__file__).resolve().parents[1]

#: shared preamble: a 2-layer network (divisible C: 8 -> 4 on a 4-way
#: column axis) + a non-divisible single-layer net (C=5 -> replication
#: fallback), sparse volley batch, single-device reference outputs.
SETUP = """
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.core import coding, layer, network, neuron, policy
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
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SETUP) + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_layer_and_network_bit_exact_all_backends():
    """layer_forward + network.forward on a (2, 4) mesh == single device
    for every jnp engine, including the non-divisible column fallback."""
    print(_run("""
        for backend in ("scan", "closed_form", "event"):
            for cfg0, ps in ((net, params), (odd, podd)):
                bnet = network.make_network(
                    [dataclasses.replace(lc, backend=backend)
                     for lc in cfg0.layers])
                sp = jax.device_put(ps, network.param_shardings(bnet, mesh))
                fwd = jax.jit(
                    lambda p, x, n=bnet: network.forward(p, x, n)[:2])
                # property-style: several random draws, incl. an all-silent
                # and a fully-dense volley batch (padding/no-WTA edges)
                draws = [sparse_volleys(np.random.default_rng(s), 8,
                                        cfg0.n_inputs) for s in range(3)]
                draws.append(np.full((8, cfg0.n_inputs), NS, np.int32))
                draws.append(np.asarray(
                    np.random.default_rng(7).integers(
                        0, 12, size=(8, cfg0.n_inputs)), np.int32))
                for volleys in draws:
                    rres = network.forward(ps, volleys, bnet)
                    ref, ref_win = np.asarray(rres.out), rres.winners
                    with compat.set_mesh(mesh):
                        vs = jax.device_put(
                            volleys, network.data_sharding(bnet, mesh,
                                                           volleys.shape[0]))
                        out, win = fwd(sp, vs)
                    np.testing.assert_array_equal(np.asarray(out), ref)
                    for w_ref, w_sh in zip(ref_win, win):
                        np.testing.assert_array_equal(np.asarray(w_sh),
                                                      np.asarray(w_ref))
        print('SHARDED_FWD_BIT_EXACT_OK')
    """))


def test_sharded_layer_step_training_bit_exact():
    """layer_step (forward + minibatch STDP) matches on the mesh: the
    training path inherits the same constraints as the forward path."""
    print(_run("""
        w = jnp.round(params[0]).astype(jnp.float32)
        ref_w, ref_out, ref_win = layer.layer_step(w, jnp.asarray(v), l1)
        sw = jax.device_put(w, network.param_shardings(net, mesh)[0])
        with compat.set_mesh(mesh):
            vs = jax.device_put(v, network.data_sharding(net, mesh, 8))
            new_w, out, win = jax.jit(
                lambda p, x: layer.layer_step(p, x, l1))(sw, vs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
        np.testing.assert_array_equal(np.asarray(win), np.asarray(ref_win))
        np.testing.assert_allclose(np.asarray(new_w), np.asarray(ref_w),
                                   rtol=1e-6, atol=1e-6)
        print('SHARDED_STEP_BIT_EXACT_OK')
    """))


def test_sharded_engine_serve_bit_exact():
    """TNNEngine.serve with a mesh == unbatched single-device reference;
    ``auto`` resolves once, inside the engine's mesh scope."""
    print(_run("""
        from repro.serve import tnn_engine
        streams = [v[:3], v[3:6], v[6:], v[1:2]]
        eng = tnn_engine.TNNEngine(
            params, net, tnn_engine.TNNServeConfig(n_slots=3), mesh=mesh)
        results = eng.serve(streams)
        for s, r in zip(streams, results):
            np.testing.assert_array_equal(
                tnn_engine.reference_outputs(params, net, s), r)
        st = eng.stats()
        assert st['n_retired'] == 4.0
        assert eng.engine == 'closed_form'  # CPU here
        print('SHARDED_ENGINE_BIT_EXACT_OK')
    """))


def test_pallas_mesh_capability_model():
    """Per-kernel mesh capability (DESIGN.md §6.4): under an active mesh
    the Pallas engines run exactly when the column stack tiles the
    ``column`` axis (shard_map fast path, kernels/rnl_shard). For 2-D
    banks, unknown shapes, and non-dividing C an explicit Pallas request
    raises, while ``auto`` degrades to the bit-exact closed form — and
    the serve engine records whichever engine it resolved to."""
    print(_run("""
        cfgn = l1.neuron_config()
        times_rf = jnp.swapaxes(jnp.asarray(v)[:, l1.rf_index()], 0, 1)
        w = jnp.round(params[0]).astype(jnp.int32)
        ref = np.asarray(neuron.fire_times_bank(times_rf, w, cfgn,
                                                backend='closed_form'))
        with compat.set_mesh(mesh):
            assert neuron.mesh_active()
            # capability through resolve_engine(): C=8 tiles the 4-way
            # column axis; C=5 and 2-D banks (no column axis) do not
            assert policy.resolve_engine('pallas', 8) == 'pallas'
            assert policy.resolve_engine(
                'pallas_compact', (8, 4)) == 'pallas_compact'
            # unknown / non-dividing shapes: an explicit Pallas request
            # raises instead of switching engines behind the caller's back
            for name, counts in (('pallas', None), ('pallas', 5),
                                 ('pallas_compact', 5)):
                try:
                    policy.resolve_engine(name, counts)
                except ValueError as e:
                    assert "backend='auto'" in str(e), e
                else:
                    raise AssertionError((name, counts))
            # every engine stays bit-exact through the dispatch
            for backend in ('pallas', 'pallas_compact', 'auto'):
                got = neuron.fire_times_bank(times_rf, w, cfgn,
                                             backend=backend)
                np.testing.assert_array_equal(np.asarray(got), ref)
            # auto -> pallas needs a TPU backend AND the capability
            assert policy.resolve_engine('auto', 8) == 'closed_form'  # CPU
            jb, jax.default_backend = jax.default_backend, lambda: 'tpu'
            try:
                assert policy.resolve_engine('auto', 8) == 'pallas'
                # non-dividing C on "TPU": no pallas, the closed form
                assert policy.resolve_engine('auto', 5) == 'closed_form'
            finally:
                jax.default_backend = jb
        assert not neuron.mesh_active()
        assert policy.resolve_engine('pallas') == 'pallas'
        from repro.serve import tnn_engine
        # dividing columns (8, 4): the requested engine really runs
        eng = tnn_engine.TNNEngine(
            params, net,
            tnn_engine.TNNServeConfig(n_slots=2, backend='pallas'),
            mesh=mesh)
        for s, r in zip([v[:2]], eng.serve([v[:2]])):
            np.testing.assert_array_equal(
                tnn_engine.reference_outputs(params, net, s), r)
        assert eng.engine == 'pallas'
        assert all(lc.backend == 'pallas' for lc in eng.net.layers)
        # non-dividing C=5: an explicit pallas engine refuses to start;
        # auto on "TPU" takes the replication fallback and records it
        try:
            tnn_engine.TNNEngine(
                podd, odd,
                tnn_engine.TNNServeConfig(n_slots=2, backend='pallas'),
                mesh=mesh)
        except ValueError:
            pass
        else:
            raise AssertionError('explicit pallas with C=5 must raise')
        jb, jax.default_backend = jax.default_backend, lambda: 'tpu'
        try:
            engo = tnn_engine.TNNEngine(
                podd, odd, tnn_engine.TNNServeConfig(n_slots=2), mesh=mesh)
            served = engo.serve([vodd[:2]])
        finally:
            jax.default_backend = jb
        for s, r in zip([vodd[:2]], served):
            np.testing.assert_array_equal(
                tnn_engine.reference_outputs(podd, odd, s), r)
        assert engo.engine == 'closed_form', engo.engine
        assert engo.stats()['n_steps'] > 0
        print('PALLAS_MESH_CAPABILITY_OK')
    """))


def test_sharded_pipelined_forward_bit_exact():
    """network.forward(..., microbatches=M) on the (2, 4) mesh == the
    single-device
    barriered reference for every jnp engine and micro-batch split (incl.
    ragged 8 % 3 != 0 and M > B) — the §5.4 schedule composes with the
    §6.4/§6.5 placement without changing a spike time. The tick scan runs
    rolled under the mesh, so this also guards the cross-layer stage carry
    of the while loop against an SPMD layout miscompile."""
    print(_run("""
        from repro.serve import tnn_engine
        for backend in ("scan", "closed_form", "event"):
            bnet = network.make_network(
                [dataclasses.replace(lc, backend=backend)
                 for lc in net.layers])
            sp = jax.device_put(params, network.param_shardings(bnet, mesh))
            rres = network.forward(params, v, bnet)
            ref, ref_win = np.asarray(rres.out), rres.winners
            for m in (1, 2, 3, 8, 20):
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
        # serve path: mesh + pipeline_microbatches together
        streams = [v[:3], v[3:6], v[6:]]
        eng = tnn_engine.TNNEngine(
            params, net,
            tnn_engine.TNNServeConfig(n_slots=3, pipeline_microbatches=3),
            mesh=mesh)
        for s, r in zip(streams, eng.serve(streams)):
            np.testing.assert_array_equal(
                tnn_engine.reference_outputs(params, net, s), r)
        assert eng.stats()['pipeline_microbatches'] == 3.0
        print('SHARDED_PIPELINED_BIT_EXACT_OK')
    """))


def test_sharded_recurrent_carry_bit_exact():
    """Recurrent carry threading on the (2, 4) mesh == the single-device
    unrolled reference: the carry rides the same ('data',)/('column',)
    stage placement (sharding.specs.tnn_carry_*), for the dividing C=8
    stack AND the C=5 replication fallback, across multiple cycles and
    composed with the pipelined schedule."""
    print(_run("""
        rl1 = dataclasses.replace(l1, recurrent=True)
        rl2 = dataclasses.replace(l2, recurrent=True)
        rnet = network.make_network([rl1, rl2])
        rodd = network.make_network(
            [dataclasses.replace(rl1, n_columns=5)])
        for cfg0, key in ((rnet, 0), (rodd, 1)):
            ps = network.init_network(jax.random.PRNGKey(key), cfg0)
            seq = [sparse_volleys(np.random.default_rng(s), 8,
                                  cfg0.n_inputs) for s in range(3)]
            seq.append(np.full((8, cfg0.n_inputs), NS, np.int32))
            # single-device reference: explicit multi-cycle carry thread
            ref_outs, carry = [], None
            for vol in seq:
                res = network.forward(ps, jnp.asarray(vol), cfg0,
                                      carry=carry)
                ref_outs.append(np.asarray(res.out))
                carry = res.carry
            ref_carry = [np.asarray(c) for c in carry]
            sp = jax.device_put(ps, network.param_shardings(cfg0, mesh))
            for m in (1, 3):
                carry_sh = None
                with compat.set_mesh(mesh):
                    for vol, want in zip(seq, ref_outs):
                        vs = jax.device_put(
                            vol, network.data_sharding(cfg0, mesh,
                                                       vol.shape[0]))
                        res = network.forward(sp, vs, cfg0,
                                              carry=carry_sh,
                                              microbatches=m)
                        np.testing.assert_array_equal(
                            np.asarray(res.out), want)
                        carry_sh = res.carry
                for got, want in zip(carry_sh, ref_carry):
                    np.testing.assert_array_equal(np.asarray(got), want)
        # engine + mesh: recurrent streams through the slot pool
        from repro.serve import tnn_engine
        rparams = network.init_network(jax.random.PRNGKey(0), rnet)
        streams = [v[:3], v[3:6], v[6:], v[2:4]]
        eng = tnn_engine.TNNEngine(
            rparams, rnet, tnn_engine.TNNServeConfig(n_slots=3),
            mesh=mesh)
        assert eng.stateful
        for s, r in zip(streams, eng.serve(streams)):
            np.testing.assert_array_equal(
                tnn_engine.reference_outputs(rparams, rnet, s), r)
        print('SHARDED_RECURRENT_BIT_EXACT_OK')
    """))


def test_sharded_learn_while_serving_and_crash_recovery():
    """Learn-while-serving on the (2, 4) mesh (DESIGN.md §5.5 + §6.4):
    STDP updates stay column-sharded step after step (layer_step pins the
    new stacks via specs.tnn_param_axes — no silent gather to one
    device), the learned weights match the single-device learning engine,
    and serve_resilient's restore-and-replay restores snapshots INTO the
    mesh placement — outputs bit-exact vs the uninterrupted sharded run."""
    print(_run("""
        import tempfile
        from jax.sharding import PartitionSpec as P
        from repro.serve import tnn_engine
        from repro.train import fault_tolerance as FT

        streams = [v[:3], v[3:6], v[6:], v[1:2], v[4:6]]
        scfg = lambda **kw: tnn_engine.TNNServeConfig(
            n_slots=2, backend='closed_form', **kw)

        # single-device learning reference
        ref_eng = tnn_engine.TNNEngine(params, net, scfg(learn=True))
        ref_res = ref_eng.serve(streams)

        eng = tnn_engine.TNNEngine(params, net, scfg(learn=True),
                                   mesh=mesh)
        results = eng.serve(streams)
        assert eng.n_stdp_updates == eng.n_steps > 0
        # weight state is STILL column-sharded after every update
        assert eng.params[0].sharding.spec == P('column', None, None)
        assert eng.params[1].sharding.spec == P('column', None, None)
        for a, b in zip(results, ref_res):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(eng.params, ref_eng.params):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

        # crash recovery under the mesh: learning-off outputs bit-exact,
        # snapshots restore into the sharded placement
        ref_off = tnn_engine.TNNEngine(params, net, scfg(),
                                       mesh=mesh).serve(streams)
        with tempfile.TemporaryDirectory() as d:
            eng2 = tnn_engine.TNNEngine(
                params, net,
                scfg(checkpoint_dir=d, checkpoint_every=2,
                     checkpoint_keep=100, checkpoint_async=False),
                mesh=mesh)
            def boom(step_id, fired=[False]):
                if step_id >= 3 and not fired[0]:
                    fired[0] = True
                    raise FT.WorkerFailure(5, '(injected)')
            r2, report = tnn_engine.serve_resilient(
                eng2, streams, failure_injector=boom)
            assert report['restarts'] == 1 and eng2.n_restores == 1
            assert eng2.params[0].sharding.spec == P('column', None, None)
            for a, b in zip(r2, ref_off):
                np.testing.assert_array_equal(a, b)

            # learning on: restored run == deterministic replay from the
            # snapshot step, still sharded
            with tempfile.TemporaryDirectory() as d2:
                eng3 = tnn_engine.TNNEngine(
                    params, net,
                    scfg(learn=True, checkpoint_dir=d2, checkpoint_every=2,
                         checkpoint_keep=100, checkpoint_async=False),
                    mesh=mesh)
                def boom2(step_id, fired=[False]):
                    if step_id >= 3 and not fired[0]:
                        fired[0] = True
                        raise FT.WorkerFailure(6, '(injected)')
                r3, rep3 = tnn_engine.serve_resilient(
                    eng3, streams, failure_injector=boom2)
                from repro.train import checkpoint as CKPT
                s = rep3['restored_steps'][0]
                snap = CKPT.restore_checkpoint(
                    d2,
                    {'params': tuple(eng3.params),
                     'counters': np.zeros(2, np.int32)}, s)
                eng4 = tnn_engine.TNNEngine(snap['params'], net,
                                            scfg(learn=True), mesh=mesh)
                eng4.step_id = s
                eng4.n_stdp_updates = int(np.asarray(snap['counters'])[1])
                eng4.serve([streams[i] for i in rep3['resubmitted'][0]])
                for a, b in zip(eng3.params, eng4.params):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
        print('SHARDED_LEARN_SERVE_OK')
    """))


def test_sharded_init_network_matches_unsharded():
    """init_network(mesh=...) is bit-identical to the unsharded init and
    places each layer under its column spec (replication when C doesn't
    divide the axis)."""
    print(_run("""
        from jax.sharding import PartitionSpec as P
        sp = network.init_network(jax.random.PRNGKey(0), net, mesh=mesh)
        for a, b in zip(sp, params):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert sp[0].sharding.spec == P('column', None, None)   # C=8 % 4 == 0
        so = network.init_network(jax.random.PRNGKey(1), odd, mesh=mesh)
        assert so[0].sharding.spec == P(None, None, None)       # C=5 -> repl
        print('SHARDED_INIT_OK')
    """))


def test_tnn_mesh_factory_validation():
    """tnn_mesh shapes + error paths (needs the 8 fake devices)."""
    print(_run("""
        m = SH.tnn_mesh()                       # all devices on column
        assert dict(m.shape) == {'data': 1, 'column': 8}
        m = SH.tnn_mesh(2, 4)
        assert dict(m.shape) == {'data': 4, 'column': 2}
        try:
            SH.tnn_mesh(n_data=3)               # 3 does not divide 8
        except ValueError:
            pass
        else:
            raise AssertionError('expected ValueError')
        try:
            SH.tnn_mesh(16, 1)                  # more than available
        except ValueError:
            pass
        else:
            raise AssertionError('expected ValueError')
        for bad in ((0, 1), (4, 0), (-2, 1)):   # zero-size axes rejected
            try:
                SH.tnn_mesh(*bad)
            except ValueError:
                pass
            else:
                raise AssertionError(f'expected ValueError for {bad}')
        print('TNN_MESH_FACTORY_OK')
    """))
