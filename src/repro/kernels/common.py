"""Shared kernel plumbing: interpret-mode selection and tiling helpers.

TPU v5e is the target. On a CPU backend (the test suite) kernels default
to ``interpret=True``: the Pallas interpreter executes the kernel body in
Python for bit-accurate validation. On a TPU backend the same
``pl.pallas_call`` lowers to Mosaic.
"""

from __future__ import annotations

import os

import jax

LANE = 128          # TPU vector lane width (last dim tiling quantum)
SUBLANE = 8         # float32 sublane quantum (second-to-last dim)


def env_flag(var: str, default: bool = False) -> bool:
    """Strict boolean env knob: ``"1"`` / ``"0"`` only.

    Unset (or empty — the shell's way of unsetting) returns ``default``;
    anything else raises. A truthy-ing parse once made
    ``REPRO_PALLAS_INTERPRET=false`` force the interpreter ON — a silent
    inversion this helper (and the repro-lint ``raw-env`` rule pushing
    callers through it) makes impossible.
    """
    raw = os.environ.get(var)
    if raw is None or raw == "":
        return default
    if raw == "1":
        return True
    if raw == "0":
        return False
    raise ValueError(
        f"{var}={raw!r}: expected '0' or '1' (unset/empty = default)")


def env_choice(var: str, choices: tuple, default: str) -> str:
    """Strict enumerated env knob: the value must be one of ``choices``.

    Unset (or empty) returns ``default``; anything outside the set raises
    instead of flowing downstream as a dispatch key that fails late (or
    never — ``REPRO_KERNEL_IMPL=pallaz`` used to select nothing and fall
    through to whichever branch compared last).
    """
    raw = os.environ.get(var)
    if raw is None or raw == "":
        return default
    if raw not in choices:
        raise ValueError(f"{var}={raw!r}: expected one of {choices}")
    return raw


def use_interpret() -> bool:
    """Whether ``pl.pallas_call`` should run the Pallas interpreter.

    Explicit override first: ``REPRO_PALLAS_INTERPRET=1`` forces the
    interpreter (CI's shard-tests lane uses this to exercise the shard_map
    kernel path on host devices), ``=0`` forces real compilation (e.g. to
    verify Mosaic lowering on a TPU pod); any other value raises
    (:func:`env_flag` — ``=false`` used to silently force the interpreter
    ON). ``REPRO_KERNEL_INTERPRET`` is honored as a legacy alias. With
    neither set, sniff the backend: CPU interprets, TPU compiles.
    Deliberately uncached so tests can flip the env between
    subprocess-free calls (each jit specialization bakes the value it saw
    at trace time).
    """
    for var in ("REPRO_PALLAS_INTERPRET", "REPRO_KERNEL_INTERPRET"):
        if os.environ.get(var) not in (None, ""):
            return env_flag(var)
    return jax.default_backend() == "cpu"


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b
