"""Production training launcher.

Composes the full stack: arch config -> mesh -> sharded train step ->
deterministic data pipeline -> checkpoint/restart -> heartbeat monitor.
On a real TPU fleet this binary runs per host (jax.distributed handles
process groups); on this CPU container use ``--smoke`` (reduced config,
1-device mesh) — the code path is identical.

Usage:
  python -m repro.launch.train --arch internlm2-1.8b --smoke --steps 50
  python -m repro.launch.train --arch arctic-480b --steps 1000 \
      --ckpt-dir /ckpts/arctic --compress --opt      # fleet deployment
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.data import pipeline as DP
from repro.launch import mesh as mesh_lib
from repro.optim import grad_compression as GC
from repro.optim.optimizers import AdamWConfig
from repro.sharding import specs as SH
from repro.train import checkpoint as CK
from repro.train import fault_tolerance as FT
from repro.train import train_loop as TL


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + small batch (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress", action="store_true",
                    help="Catwalk top-k gradient compression")
    ap.add_argument("--opt", action="store_true",
                    help="hillclimbed layout (see dryrun.apply_opt)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default="synthetic",
                    help="'synthetic' or a path to a uint16 token memmap")
    args = ap.parse_args()

    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    if args.opt:
        from repro.launch.dryrun import apply_opt
        cfg = apply_opt(cfg)
    seq = args.seq_len or (128 if args.smoke else 4096)
    gbatch = args.global_batch or (8 if args.smoke else 256)
    n_hosts = max(1, jax.process_count())
    host = jax.process_index()

    print(f"[train] {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"seq {seq}, global batch {gbatch}, {jax.device_count()} devices")

    tcfg = TL.TrainConfig(
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                              total_steps=args.steps),
        grad_accum=args.grad_accum,
        compression=GC.CompressionConfig(rho=0.01) if args.compress
        else None)
    state = TL.init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    step_fn = TL.make_train_step(cfg, tcfg)

    # mesh + shardings when >1 device (smoke: single device, plain jit)
    if jax.device_count() > 1:
        model_par = min(16, jax.device_count())
        data_par = jax.device_count() // model_par
        mesh = mesh_lib.make_dev_mesh(data_par, model_par)
        state_shape = jax.eval_shape(
            lambda: TL.init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
        st_sh = SH.param_shardings(state_shape, mesh,
                                   replicate_embed=cfg.batch_over_model)
        ctx = SH.compat.set_mesh(mesh)
        ctx.__enter__()
        state = jax.device_put(state, st_sh)
        step_fn = jax.jit(step_fn, in_shardings=(st_sh, None),
                          donate_argnums=(0,))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    dcfg = DP.DataConfig(seq_len=seq, global_batch=gbatch,
                         vocab_size=cfg.vocab_size, n_hosts=n_hosts,
                         host_id=host)
    data = (DP.SyntheticLM(dcfg) if args.data == "synthetic"
            else DP.MemmapCorpus(args.data, dcfg))

    mgr = CK.CheckpointManager(args.ckpt_dir, keep=3, every=args.ckpt_every,
                               async_save=True)
    state, start = mgr.restore_latest(state)
    if start:
        print(f"[train] resumed from step {start}")
    monitor = FT.HeartbeatMonitor(n_hosts)

    losses = []
    t0 = time.time()
    for i in range(start, args.steps):
        ts = time.time()
        state, metrics = step_fn(state, data.batch(i))
        monitor.beat(host, time.time() - ts)
        losses.append(float(metrics["loss"]))
        mgr.maybe_save(i + 1, state)
        if (i + 1) % 10 == 0:
            stragglers = monitor.stragglers()
            extra = f" STRAGGLERS={stragglers}" if stragglers else ""
            print(f"[train] step {i + 1:5d} loss {losses[-1]:.4f} "
                  f"lr {float(metrics['lr']):.2e}{extra}", flush=True)
    mgr.wait()
    dt = time.time() - t0
    done = len(losses)
    print(f"[train] {done} steps in {dt:.1f}s; "
          f"loss {np.mean(losses[:5]):.3f} -> {np.mean(losses[-5:]):.3f}")


if __name__ == "__main__":
    main()
