"""Where jax keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/serve_*.py``) call
:func:`enable` once, before their first compile. A cache only hits when
it is found at the same path again, so the location is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax reads it
at import, and nothing else is set), otherwise ``<repo>/.jax_cache``
inside the checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import pathlib

import jax

#: in-tree fallback location (src/repro/launch/ -> repository root)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
