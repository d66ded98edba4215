"""The compile-cache helper (``repro.launch.compile_cache.enable``).

jax reads ``JAX_COMPILATION_CACHE_DIR`` when it is imported, so each
case runs in a fresh interpreter with its own environment.
"""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]

PROBE = ("import jax\n"
         "from repro.launch import compile_cache\n"
         "print(compile_cache.enable())\n"
         "print(jax.config.jax_compilation_cache_dir)\n")


def _probe(cache_env):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_env_cache_dir_is_honoured(tmp_path):
    want = str(tmp_path / "xla_cache")
    assert _probe(want) == [want, want]


def test_default_cache_dir_is_in_tree():
    want = str(REPO / ".jax_cache")
    assert _probe(None) == [want, want]
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
