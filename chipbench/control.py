"""The control: show that the check fails a path one precision below.

    python chipbench/control.py --workload <cell> --seeds 1,2,3
        [--seconds 4] [--program]

Runs the cell's traffic at its own size and load with the plain reference in
the program's place, one precision below the configuration's (the system's
``control_engine``; for the TNN its weights in bfloat16, below float32), and
prints one JSON line per seed with every number the check compared and its
limit; each line must read ``correct: false``. With ``--program`` it runs the
program instead, on the same seeds, for the readings of sound runs. All seeds
run in one process, so set-up is paid once. Needs a TPU, like ``run.py``; the
benchmark's own runs never run it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args()

    import jax

    from harness import runner, spec

    cell = spec.find_cell(spec.load_benchmark(), args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path.insert(0, str(ROOT / "src"))
    if args.program:
        engine = cell.system.engine
    else:
        def engine(params, config, name):
            return cell.system.control_engine(params, config)
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = runner.run(cell, seed, args.seconds, False, time.perf_counter(),
                         engine=engine, log=functools.partial(
                             print, file=sys.stderr, flush=True))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "engine": "program" if args.program else
                          "control", "correct": res["correct"],
                          "attempted": res["attempted"],
                          "readings": res["readings"],
                          "compared": res["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
