"""Sharding-layout auditor (DESIGN.md §7.2): clean on the shipped tree
under the 2x4 host mesh, and LOUD when the PR-6 maybe_wsc swapped-zip
bug is re-injected (the regression this auditor exists to catch).

Subprocess-isolated like tests/test_sharding_tnn.py: the audit needs 8
host devices (XLA_FLAGS), which must be set before jax initialises."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

AUDIT = """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.analysis import layout_audit
    from repro.sharding import specs as sharding_specs

    # ---- clean tree: every scenario, zero mismatches -------------------
    rep = layout_audit.run_audit()
    assert rep.checked, "auditor fired no checks"
    assert not rep.mismatches, rep.render()
    n_clean = len(rep.checked)

    # ---- re-inject the PR-6 swapped-zip bug ----------------------------
    # maybe_wsc zipping (spec, shape) instead of (shape, spec) resolved
    # every constraint to replication; the auditor must name the tensor
    # and show expected vs actual.
    orig = sharding_specs.maybe_wsc

    def buggy_wsc(x, *spec):
        am = sharding_specs.compat.get_abstract_mesh()
        if not am.axis_names:
            return x
        resolved = P(*(sharding_specs.ambient_fit(d, e)
                       for d, e in zip(spec, x.shape)))
        return jax.lax.with_sharding_constraint(x, resolved)

    sharding_specs.maybe_wsc = buggy_wsc
    try:
        bad = layout_audit.run_audit(scenarios=("forward",))
    finally:
        sharding_specs.maybe_wsc = orig
    assert bad.mismatches, "auditor missed the re-injected layout bug"
    text = bad.render()
    assert "MISMATCH" in text
    assert "expected=" in text and "actual=" in text
    assert any(r.label for r in bad.mismatches)
    print(f"AUDIT_OK clean={n_clean} buggy={len(bad.mismatches)}")
"""

CLI_BUGGY = """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.analysis import layout_audit
    from repro.sharding import specs as sharding_specs

    def buggy_wsc(x, *spec):
        am = sharding_specs.compat.get_abstract_mesh()
        if not am.axis_names:
            return x
        resolved = P(*(sharding_specs.ambient_fit(d, e)
                       for d, e in zip(spec, x.shape)))
        return jax.lax.with_sharding_constraint(x, resolved)

    sharding_specs.maybe_wsc = buggy_wsc
    raise SystemExit(layout_audit.main(["--scenarios", "forward"]))
"""


def _env():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    env["REPRO_PALLAS_INTERPRET"] = "1"
    return env


def test_audit_clean_tree_and_catches_swapped_zip():
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(AUDIT)],
        capture_output=True, text=True, env=_env(), timeout=600)
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    assert "AUDIT_OK" in out.stdout


def test_audit_cli_exit_codes():
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis.layout_audit",
         "--scenarios", "forward"],
        capture_output=True, text=True, env=_env(), timeout=600)
    assert ok.returncode == 0, (ok.stdout + ok.stderr)[-4000:]
    bad = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CLI_BUGGY)],
        capture_output=True, text=True, env=_env(), timeout=600)
    assert bad.returncode == 1, (bad.stdout + bad.stderr)[-4000:]
    assert "MISMATCH" in bad.stdout + bad.stderr
