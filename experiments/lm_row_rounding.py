"""Why a one-slot LM serve can differ from a batched one on a TPU.

Measures, on the chip, the rounding behind DESIGN.md §5.2's known limit,
with ``internlm2-1.8b`` at its published widths and random weights from
``--seed``:

  matmul  row 0 of ``x @ w`` (bf16 out and f32 out) for three of the
          model's weights at M=1 against M in {2, 4, 8, 16};
  decode  teacher-forced decode logits of row 0 at batch 1 against
          batches 2, 4 and 8, the top-1/top-2 margins at batch 1, and
          where the greedy token flips;
  serve   each request served alone through n_slots in {1, 4, 8} against
          the batched serve through 4 and 8 slots.

Prints one line per reading and checks nothing. Needs a TPU; exits 2
elsewhere. Run from the repository root:
    python experiments/lm_row_rounding.py [--seed 0]
"""

from __future__ import annotations

import argparse
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

MAX_NEW = 16


def _matmul_rows(params) -> None:
    mm = jax.jit(lambda a, b: a @ b)
    mmf = jax.jit(lambda a, b: jnp.dot(a, b,
                                       preferred_element_type=jnp.float32))
    mlp = params["layers"]["mlp"]
    for name, w in (("w_up", mlp["w_up"][0]), ("w_down", mlp["w_down"][0]),
                    ("lm_head", params["lm_head"])):
        x = jax.random.normal(jax.random.PRNGKey(1), (16, w.shape[0]),
                              jnp.float32).astype(jnp.bfloat16)
        for f, tag in ((mm, "bf16 out"), (mmf, "f32 out")):
            base = np.asarray(f(x[:1], w)[0], np.float32)
            res = []
            for m in (2, 4, 8, 16):
                r = np.asarray(f(x[:m], w)[0], np.float32)
                res.append(f"M={m} equal={np.array_equal(base, r)} "
                           f"max|d|={np.max(np.abs(base - r)):.3g}")
            print(f"[matmul] {name} {tag}, row 0 vs M=1: {'; '.join(res)}",
                  flush=True)


def _decode_logits(params, cfg, eng, prompt) -> None:
    from repro.models import transformer as T

    seq = np.concatenate([prompt, eng.serve([prompt], MAX_NEW)[0][:-1]])
    step = jax.jit(lambda p, st, t: T.decode_step(p, cfg, st, t))
    fill = min(2, cfg.vocab_size - 1)
    logits = {}
    for b in (1, 2, 4, 8):
        st = T.per_slot_state(
            T.init_serve_state(params, cfg, b, eng.scfg.max_len), b)
        rows = []
        for tkn in seq:
            toks = np.full((b, 1), fill, np.int32)
            toks[0, 0] = tkn
            lg, st = step(params, st, toks)
            rows.append(np.asarray(lg[0], np.float32))
        logits[b] = np.stack(rows)
    for b in (2, 4, 8):
        d = np.abs(logits[1] - logits[b]).max(axis=-1)
        first = int(np.argmax(d > 0)) if (d > 0).any() else -1
        print(f"[decode] row 0 logits B=1 vs B={b}: first differing step "
              f"{first} of {len(seq)}, max |d| {d.max():.4g}", flush=True)
    gen = logits[1][len(prompt) - 1:]
    top2 = np.sort(gen, axis=-1)[:, -2:]
    print(f"[decode] B=1 top-1/top-2 margins per generated token: "
          f"{np.round(top2[:, 1] - top2[:, 0], 4).tolist()}", flush=True)
    for b in (2, 4, 8):
        same = logits[b][len(prompt) - 1:].argmax(-1) == gen.argmax(-1)
        print(f"[decode] greedy token B={b} equals B=1: "
              f"{same.astype(int).tolist()}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"lm_row_rounding: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "src"))
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.serve import Engine, ServeConfig

    cfg = get_config("internlm2-1.8b")
    params = jax.jit(lambda k: T.init_params(k, cfg))(
        jax.random.PRNGKey(args.seed))
    # the prompts chip_smoke.py's LM phase serves
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(3, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in rng.integers(4, 17, size=6)]
    eng = Engine(params, cfg, ServeConfig(
        max_len=max(len(p) for p in prompts) + MAX_NEW + 2))
    print(f"{dev.device_kind}; jax {jax.__version__}; {cfg.name}; seed "
          f"{args.seed}", flush=True)

    _matmul_rows(params)
    _decode_logits(params, cfg, eng, prompts[3])
    for nb in (4, 8):
        got = eng.serve(prompts, MAX_NEW, n_slots=nb)
        for alone_slots in (1, nb):
            alone = [eng.serve([p], MAX_NEW, n_slots=alone_slots)[0]
                     for p in prompts]
            same = [bool(np.array_equal(a, g)) for a, g in zip(alone, got)]
            print(f"[serve] batched {nb} slots vs each alone in "
                  f"{alone_slots} slot(s): equal {same}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
