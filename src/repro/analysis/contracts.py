"""Runtime contract guards: compile-count and tracer-hygiene assertions.

The serve path's "weight updates never recompile" contract (DESIGN.md
§5.5) was asserted only indirectly — ``stats()["jit_variants"]`` counts
cached entries, not compiles, so a step that recompiled the *same*
variant every call would pass. These guards watch the real signal
(DESIGN.md §7.3):

  * :func:`assert_max_compiles` — context manager counting XLA backend
    compiles inside the block via ``jax.monitoring``'s
    ``/jax/core/compile/backend_compile_duration`` events (one per
    backend compile, zero on cache hits). Because eager jnp ops also
    compile on first touch, steady-state contracts should warm up
    OUTSIDE the guard and then assert ``assert_max_compiles(0)``.
  * :func:`assert_no_tracer_leaks` — a gc-walk canary for jax tracers
    that outlive their trace (the failure mode behind host-side policy
    code capturing a traced value).

Both are exposed as pytest fixtures (``max_compiles_guard``,
``tracer_leak_check``) via ``tests/conftest.py``.
"""

from __future__ import annotations

import contextlib
import gc
import threading
from typing import Iterator, List, Optional

from repro.sharding import compat

#: substring of the jax.monitoring event key fired once per XLA backend
#: compile (a duration event on every jax version the CI matrix runs)
COMPILE_EVENT = "backend_compile"

_lock = threading.Lock()
_installed = False
_compile_count = 0


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if COMPILE_EVENT in event:
        global _compile_count
        with _lock:
            _compile_count += 1


def install() -> None:
    """Register the compile-event listener (idempotent).

    jax.monitoring has no per-listener unregister, so one module-level
    listener feeds a counter for the process lifetime and the guards
    work on snapshots of it.
    """
    global _installed
    with _lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def compile_count() -> int:
    """Backend compiles observed since :func:`install` (process-wide)."""
    install()
    return _compile_count


class CompileTally:
    """Live view handed out by :func:`assert_max_compiles`."""

    def __init__(self, start: int):
        self._start = start

    @property
    def count(self) -> int:
        return _compile_count - self._start


@contextlib.contextmanager
def assert_max_compiles(n: int, label: str = "") -> Iterator[CompileTally]:
    """Fail if more than ``n`` XLA backend compiles happen in the block.

    Counts every compile the process performs while the block runs —
    including first-touch eager-op compiles — so steady-state contracts
    ("weight updates never recompile") should warm their jit variants up
    before entering the guard and assert ``n=0``::

        eng.step()                      # warmup: variant compiles here
        with contracts.assert_max_compiles(0, "serve-learn steady state"):
            for _ in range(49):
                eng.step()
    """
    install()
    tally = CompileTally(_compile_count)
    yield tally
    actual = tally.count
    if actual > n:
        where = f" [{label}]" if label else ""
        raise AssertionError(
            f"compile-count contract{where}: {actual} backend compile(s) "
            f"inside the guarded block, at most {n} allowed — something "
            "is retracing (changed static args / weak types / new shapes "
            "reaching jit)")


def live_tracers() -> List[object]:
    """All jax tracers currently reachable via the gc (post-collect).

    A non-empty result outside an active trace means some host-side
    structure captured a traced value — the leak that turns into a
    ``TracerLeakError``/``UnexpectedTracerError`` only when the capture
    is later *used*, often far from the offending code.
    """
    gc.collect()
    return [o for o in gc.get_objects() if compat.is_tracer(o)]


@contextlib.contextmanager
def assert_no_tracer_leaks(label: str = "") -> Iterator[None]:
    """Fail if the block leaves NEW jax tracers reachable after it exits.

    Pre-existing leaks (from earlier tests in the process) are excluded
    by identity snapshot, so the canary composes with any suite order.
    """
    before = {id(t) for t in live_tracers()}
    yield
    leaked = [t for t in live_tracers() if id(t) not in before]
    if leaked:
        where = f" [{label}]" if label else ""
        kinds = sorted({type(t).__name__ for t in leaked})
        raise AssertionError(
            f"tracer-leak canary{where}: {len(leaked)} tracer(s) still "
            f"reachable after the block ({', '.join(kinds)}) — a "
            "host-side structure captured a traced value")


# ------------------------------------------------------- pytest fixtures
# Imported by tests/conftest.py (kept import-guarded so the module stays
# usable without pytest installed, e.g. from the CLI auditor).
try:  # pragma: no cover - exercised through the test suite itself
    import pytest

    @pytest.fixture
    def max_compiles_guard():
        """Factory fixture: ``guard(n, label="")`` context manager."""
        install()
        return assert_max_compiles

    @pytest.fixture
    def tracer_leak_check():
        """Wrap the test body's hot section in a tracer-leak canary."""
        return assert_no_tracer_leaks
except ImportError:  # pragma: no cover
    pass


def main(argv: Optional[list] = None) -> int:
    """Tiny self-check: one jit compile is seen, a cached call is not."""
    import jax
    import jax.numpy as jnp

    install()
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.arange(8)
    f(x).block_until_ready()            # warmup (compiles)
    with assert_max_compiles(0, "cached jit call"):
        f(x).block_until_ready()
    try:
        with assert_max_compiles(0, "fresh jit call"):
            jax.jit(lambda x: x * 3)(x).block_until_ready()
    except AssertionError:
        print("contracts: ok (compile events observed and gated)")
        return 0
    print("contracts: FAILED — fresh compile went unobserved")
    return 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
