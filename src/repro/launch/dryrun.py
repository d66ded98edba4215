import os
# raw writes are the only option this early  # repro-lint: allow[raw-env]
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (jax locks device count on first init).
# pjit-partitionable path  # repro-lint: allow[raw-env]
os.environ.setdefault("REPRO_KERNEL_IMPL", "ref")

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape x mesh) cell, build the real step
function (train_step / prefill forward / decode_step), lower it against
ShapeDtypeStruct inputs with production in/out shardings, ``.compile()``
it, and record:

  * memory_analysis()  — per-device bytes (proves it fits)
  * cost_analysis()    — per-device FLOPs / bytes accessed
  * collective bytes   — parsed from the optimized (partitioned) HLO

Results append incrementally to ``experiments/dryrun/<mesh>.json`` so an
interrupted sweep resumes where it left off.

Usage:
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.sharding import compat

from repro.configs.base import SHAPES, SHAPES_BY_NAME, ModelConfig, ShapeConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.input_specs import cell_is_applicable, input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.roofline import analysis as R
from repro.sharding import specs as SH
from repro.train import train_loop as TL

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _named(mesh, tree_of_pspecs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def apply_opt(cfg: ModelConfig) -> ModelConfig:
    """Beyond-baseline layout (--opt): per-family optimized settings from
    the §Perf hillclimb. The paper-baseline layout stays the default."""
    repl = {}
    if cfg.family in ("ssm", "hybrid"):
        repl["batch_over_model"] = True      # H1: ZeRO-3, no TP activations
    elif cfg.resolved_head_dim % 128 == 0:
        # H4/H9: sequence-parallel activations pay off only when head dims
        # are 128-lane aligned (glm4/llama/internlm/arctic/deepseek);
        # measured REGRESSIONS on 80/96/64-dim MHA archs (stablelm, phi3,
        # seamless) — resharding odd head layouts costs more than the
        # halved all-reduce saves. See EXPERIMENTS §Perf H9.
        repl["act_sp"] = True
    if cfg.moe is not None:
        # H5: shard_map expert-parallel relocation dispatch
        repl["moe"] = dataclasses.replace(
            cfg.moe, dispatch="catwalk_ep",
            ep_fsdp=cfg.param_count() > 100e9)
    return dataclasses.replace(cfg, **repl)


def build_lowered(cfg: ModelConfig, shape: ShapeConfig, mesh,
                  opt: bool = False):
    """Returns the lowered computation for this cell."""
    batch_specs = input_specs(cfg, shape)

    if shape.kind == "train":
        # production train configs: microbatch large global batches; bf16
        # moments for >100B params (DESIGN.md §5 memory budget)
        big = cfg.param_count() > 100e9
        from repro.optim.optimizers import AdamWConfig
        # opt layout: EP dispatch shrinks activation temps enough to drop
        # microbatching, which de-multiplies the FSDP weight gathers (H6)
        tcfg = TL.TrainConfig(
            grad_accum=8 if (big and not opt) else 1,
            optimizer=AdamWConfig(
                moments_dtype="bfloat16" if big else "float32"))
        state_shape = jax.eval_shape(
            lambda: TL.init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
        state_sh = SH.param_shardings(state_shape, mesh,
                                      replicate_embed=cfg.batch_over_model)
        data_sh = SH.data_shardings(mesh, batch_specs,
                                    over_model=cfg.batch_over_model)
        grad_pspecs = (SH.param_pspecs(state_shape.params, mesh,
                                       replicate_embed=cfg.batch_over_model)
                       if opt else None)
        step = TL.make_train_step(cfg, tcfg, grad_pspecs=grad_pspecs)
        jitted = jax.jit(step, in_shardings=(state_sh, data_sh),
                         donate_argnums=(0,))
        return jitted.lower(state_shape, batch_specs)

    if shape.kind == "prefill":
        params_shape = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg))
        params_sh = SH.param_shardings(params_shape, mesh)
        data_sh = SH.data_shardings(mesh, batch_specs,
                                    over_model=cfg.batch_over_model)

        def prefill(params, batch):
            kwargs = {k: v for k, v in batch.items() if k != "tokens"}
            logits, _ = T.forward(params, cfg, batch["tokens"],
                                  logits_mode="last", **kwargs)
            return logits

        jitted = jax.jit(prefill, in_shardings=(params_sh, data_sh))
        return jitted.lower(params_shape, batch_specs)

    # ---- decode ----------------------------------------------------------
    params_shape = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    params_sh = SH.param_shardings(params_shape, mesh)
    b = shape.global_batch
    frames_kw = {}
    if cfg.family == "audio":
        frames_kw["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encdec.encoder_seq, cfg.frontend.d_embed), jnp.bfloat16)

    if frames_kw:
        state_shape = jax.eval_shape(
            lambda p, f: T.init_serve_state(p, cfg, b, shape.seq_len,
                                            frames=f),
            params_shape, frames_kw["frames"])
    else:
        state_shape = jax.eval_shape(
            lambda p: T.init_serve_state(p, cfg, b, shape.seq_len),
            params_shape)
    state_sh = SH.serve_shardings(state_shape, mesh)
    tok_sh = SH.data_shardings(
        mesh, {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)})["tokens"]

    def step(params, state, tokens):
        return T.decode_step(params, cfg, state, tokens)

    jitted = jax.jit(step, in_shardings=(params_sh, state_sh, tok_sh),
                     donate_argnums=(1,))
    return jitted.lower(params_shape, state_shape,
                        jax.ShapeDtypeStruct((b, 1), jnp.int32))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt: bool = False) -> dict:
    cfg = get_config(arch)
    if opt:
        cfg = apply_opt(cfg)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "chips": chips, "status": "n/a"}
    if not cell_is_applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = ("long_500k needs sub-quadratic attention; "
                        f"{arch} is full-attention (DESIGN.md §4)")
        return rec

    t0 = time.time()
    with compat.set_mesh(mesh):
        lowered = build_lowered(cfg, shape, mesh, opt=opt)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    # trip-count-aware accounting (XLA cost_analysis counts while bodies
    # ONCE — scan-over-layers under-reports by ~n_layers; hlo_cost fixes
    # this). Raw cost_analysis kept for reference.
    from repro.roofline import hlo_cost as HC
    acc = HC.analyze(hlo)
    coll = HC.collective_bytes_scaled(hlo)
    flops_pc = float(acc["flops"])
    bytes_pc = float(acc["bytes"])
    coll_pc = float(sum(v for k, v in coll.items() if k != "count"))
    mf = R.model_flops(cfg, shape)
    terms = R.compute_terms(flops_per_chip=flops_pc, bytes_per_chip=bytes_pc,
                            coll_bytes_per_chip=coll_pc, chips=chips,
                            model_flops_global=mf)
    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_chip": flops_pc, "bytes_per_chip": bytes_pc,
        "collective_bytes_per_chip": coll_pc,
        "collectives": {k: v for k, v in coll.items() if v},
        "raw_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                              "bytes": float(cost.get("bytes accessed", 0.0))},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        "model_flops_global": mf,
        "terms": {"compute_s": terms.compute_s, "memory_s": terms.memory_s,
                  "collective_s": terms.collective_s},
        "dominant": terms.dominant,
        "useful_flops_ratio": terms.useful_flops_ratio,
        "roofline_fraction": terms.roofline_fraction,
    })
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=[s.name for s in SHAPES])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells already in the results file")
    ap.add_argument("--tag", default="", help="results file suffix")
    ap.add_argument("--opt", action="store_true",
                    help="apply the hillclimbed beyond-baseline layout")
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    tag = args.tag + ("_opt" if args.opt else "")
    out_path = RESULTS_DIR / f"{mesh_name}{tag}.json"
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    cells = ([(args.arch, args.shape)] if args.arch and args.shape else
             [(a, s.name) for a in ARCH_IDS for s in SHAPES])
    for arch, shape_name in cells:
        key = f"{arch}|{shape_name}"
        if key in results and results[key].get("status") in ("ok", "skipped") \
                and not args.force:
            print(f"[skip-cached] {key}")
            continue
        print(f"[cell] {key} mesh={mesh_name} ...", flush=True)
        try:
            rec = run_cell(arch, shape_name, args.multi_pod, opt=args.opt)
        except Exception as e:  # noqa: BLE001 — record the failure and go on
            rec = {"arch": arch, "shape": shape_name, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        results[key] = rec
        out_path.write_text(json.dumps(results, indent=1))
        status = rec["status"]
        extra = (f" dominant={rec.get('dominant')} "
                 f"roofline={rec.get('roofline_fraction', 0):.3f}"
                 if status == "ok" else rec.get("reason", rec.get("error", "")))
        print(f"[done] {key}: {status} {extra}", flush=True)

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\n=== {mesh_name}: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors -> {out_path}")
    if any(r["status"] == "ok" for r in results.values()):
        print("sample memory_analysis / cost_analysis keys captured: "
              "argument/output/temp bytes, flops, bytes accessed")


if __name__ == "__main__":
    main()
