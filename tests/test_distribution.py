"""Distribution-layer tests that need >1 device: run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
must keep seeing 1 device, per the dry-run contract)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap


REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    env["REPRO_KERNEL_IMPL"] = "ref"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_ep_dispatch_matches_pjit_dispatch():
    """shard_map expert-parallel dispatch == single-device catwalk dispatch
    (same routing, one drop-free body) on a (2, 4) mesh."""
    print(_run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import get_config
        from repro.launch.mesh import make_dev_mesh
        from repro.models import moe as M, transformer as T
        from repro.sharding import compat

        cfg = get_config('deepseek-v2-lite-16b').smoke()
        mcfg = cfg.moe
        key = jax.random.PRNGKey(0)
        p = M.moe_init(key, cfg.d_model, mcfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model),
                              jnp.float32)
        ref_out, ref_aux = jax.jit(
            lambda p, x: M.moe_apply(p, x, mcfg))(p, x)

        mesh = make_dev_mesh(2, 4)
        with compat.set_mesh(mesh):
            ep_out, ep_aux = jax.jit(
                lambda p, x: M.moe_apply_ep(p, x, mcfg))(p, x)
        np.testing.assert_allclose(np.asarray(ref_out), np.asarray(ep_out),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(float(ref_aux['aux_loss']),
                                   float(ep_aux['aux_loss']), atol=1e-4)
        print('EP_DISPATCH_MATCH_OK')
    """))


def test_sharded_train_step_matches_single_device():
    """One train step on a (2, 4) mesh == the same step on 1 device."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_config
        from repro.launch.mesh import make_dev_mesh
        from repro.sharding import specs as SH
        from repro.train import train_loop as TL
        from repro.sharding import compat
        from repro.optim.optimizers import AdamWConfig

        cfg = get_config('internlm2-1.8b').smoke()
        tcfg = TL.TrainConfig(optimizer=AdamWConfig(lr=1e-2))
        state = TL.init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  cfg.vocab_size)
        batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1)}
        step = TL.make_train_step(cfg, tcfg)
        _, m_ref = jax.jit(step)(state, batch)

        mesh = make_dev_mesh(2, 4)
        state_shape = jax.eval_shape(
            lambda: TL.init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
        st_sh = SH.param_shardings(state_shape, mesh)
        with compat.set_mesh(mesh):
            state2 = TL.init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
            state2 = jax.device_put(state2, st_sh)
            data_sh = SH.data_shardings(mesh, {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in batch.items()})
            batch2 = jax.device_put(batch, data_sh)
            jstep = jax.jit(step, in_shardings=(st_sh, data_sh))
            _, m_sh = jstep(state2, batch2)
        assert abs(float(m_ref['loss']) - float(m_sh['loss'])) < 5e-2, (
            float(m_ref['loss']), float(m_sh['loss']))
        print('SHARDED_STEP_MATCH_OK')
    """))


def test_dryrun_single_cell_smoke():
    """The dry-run driver end-to-end on one small cell (256 fake devices
    inherited from dryrun's own XLA_FLAGS; subprocess isolation)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "internlm2-1.8b", "--shape", "decode_32k", "--tag", "_test",
         "--force"],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ok" in out.stdout
    res = json.loads((REPO / "experiments/dryrun/16x16_test.json").read_text())
    rec = res["internlm2-1.8b|decode_32k"]
    assert rec["status"] == "ok"
    assert rec["flops_per_chip"] > 0
    (REPO / "experiments/dryrun/16x16_test.json").unlink()


def test_mesh_factory_shapes():
    """make_production_mesh axes spec (checked without building devices)."""
    src = (REPO / "src/repro/launch/mesh.py").read_text()
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '("pod", "data", "model")' in src
