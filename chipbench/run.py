"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout; the cell, its configuration, traffic and
metrics are found by name from ``BENCHMARK.json`` (``harness/spec.py``). The
last line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``compared``: each number the check compared, beside its limit. Those
numbers are also the last lines of standard error.

Exits 2, with no result, when JAX finds no TPU or fewer chips than the cell
asks for. JAX's persistent compilation cache lives in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/`` at the
root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax

    from harness import runner, spec

    cell = spec.find_cell(spec.load_benchmark(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # cache every program, however quick to compile, so set-up is the same
    # in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path.insert(0, str(ROOT / "src"))
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
