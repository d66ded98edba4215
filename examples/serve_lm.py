"""Batched serving example: load (or init) a small LM, serve a batch of
prompts through the cached-decode engine — the same decode_step artifact
the multi-pod dry-run lowers for the (2,16,16) mesh.

Run:  PYTHONPATH=src python examples/serve_lm.py [--arch internlm2-1.8b]
      (the arch's reduced smoke config is used so it runs on CPU)
"""

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.data import tokenizer as tok
from repro.launch import compile_cache
from repro.models import transformer as T
from repro.serve import Engine, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS),
                    default="internlm2-1.8b")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch).smoke()
    print(f"serving {args.arch} (smoke config, "
          f"{cfg.param_count() / 1e3:.0f}K params)")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, ServeConfig(max_len=128,
                                          temperature=args.temperature))

    prompts = [tok.encode("the quick brown fox"),
               tok.encode("jax is"),
               tok.encode("temporal neural networks fire sparse"),
               tok.encode("hello")]
    kw = {}
    if cfg.family == "audio":
        kw["frames"] = np.random.default_rng(0).normal(
            size=(len(prompts), cfg.encdec.encoder_seq,
                  cfg.frontend.d_embed)).astype(np.float32)

    t0 = time.time()
    outs = eng.generate(prompts, max_new_tokens=args.max_new, **kw)
    dt = time.time() - t0
    total = sum(len(o) for o in outs)
    for p, o in zip(prompts, outs):
        print(f"  prompt={tok.decode(p)!r:42s} -> {len(o)} tokens "
              f"{o[:8].tolist()}...")
    print(f"\n{total} tokens for {len(prompts)} requests in {dt:.2f}s "
          f"({total / dt:.1f} tok/s batched on 1 CPU core)")


if __name__ == "__main__":
    main()
