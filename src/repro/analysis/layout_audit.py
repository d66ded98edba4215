"""Sharding layout auditor: actual vs declared PartitionSpecs (§7.2).

PR 6's post-review bug — ``maybe_wsc`` resolving every constraint to
full replication while outputs stayed bit-exact — is invisible to every
equality test in the tree. This auditor watches the layouts themselves:
it wraps :func:`repro.sharding.specs.maybe_wsc` so each pinned
intermediate gets a ``jax.debug.inspect_array_sharding`` hook, then runs
the forward / step / pipelined (and optionally Pallas) paths under the
2x4 host mesh and diffs every hook's *actual* sharding against the spec
the declared rules (:mod:`repro.sharding.specs`) resolve to — computed
independently of whatever ``maybe_wsc`` did, so a broken ``maybe_wsc``
is caught, not trusted. Output placements (post-STDP weight stacks,
post-WTA volleys) are checked the same way on the concrete results.

Failure mode is loud: non-zero exit naming each tensor (call site +
shape) with expected-vs-actual specs — replication-where-sharded reads
as ``expected P('column', 'data') / actual fully replicated``.

Run locally (sets 8 host devices for itself)::

    python -m repro.analysis.layout_audit
    python -m repro.analysis.layout_audit --scale full --n-data 2

No module-level jax import: the CLI must set ``XLA_FLAGS`` before jax
initializes, and importing this module from tests must not disturb the
host's device configuration.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import sys
import traceback
from typing import Iterator, List, Optional, Sequence, Tuple

DEFAULT_SCENARIOS = ("forward", "step", "pipelined", "pallas")


@dataclasses.dataclass
class CheckRecord:
    """One audited tensor: a maybe_wsc pin or an output placement."""

    label: str                    # call site / output name
    shape: Tuple[int, ...]
    declared: str                 # raw axis entries handed to maybe_wsc
    expected: str                 # independently resolved PartitionSpec
    actual: Optional[str] = None  # None until the hook fires
    ok: Optional[bool] = None
    scenario: str = ""

    def render(self) -> str:
        status = {True: "ok", False: "MISMATCH", None: "unchecked"}[self.ok]
        line = (f"[{self.scenario}] {self.label} shape={self.shape} "
                f"expected={self.expected}")
        if self.ok is False:
            line += f" actual={self.actual}"
        return f"{status:9s} {line}"


@dataclasses.dataclass
class AuditReport:
    records: List[CheckRecord] = dataclasses.field(default_factory=list)

    @property
    def checked(self) -> List[CheckRecord]:
        return [r for r in self.records if r.ok is not None]

    @property
    def mismatches(self) -> List[CheckRecord]:
        return [r for r in self.records if r.ok is False]

    def render(self) -> str:
        lines = [r.render() for r in self.records]
        lines.append(f"layout-audit: {len(self.checked)}/"
                     f"{len(self.records)} checks fired, "
                     f"{len(self.mismatches)} mismatch(es)")
        return "\n".join(lines)


def _call_site() -> str:
    """Innermost repro frame that is not the auditor or specs.py."""
    for fr in reversed(traceback.extract_stack()):
        fn = fr.filename.replace("\\", "/")
        if fn.endswith(("analysis/layout_audit.py", "sharding/specs.py")):
            continue
        if "/repro/" in fn:
            return f"{fn.split('/repro/')[-1]}:{fr.lineno} {fr.name}"
        if "/tests/" in fn:
            return f"tests/{fn.split('/tests/')[-1]}:{fr.lineno} {fr.name}"
    return "<unknown call site>"


@contextlib.contextmanager
def audit_scope(mesh, report: AuditReport,
                scenario: str = "") -> Iterator[AuditReport]:
    """Wrap the CURRENT ``sharding_specs.maybe_wsc`` with layout checks.

    Wrapping whatever the attribute currently points at (rather than a
    pristine copy) is deliberate: a regression test can monkeypatch a
    broken ``maybe_wsc`` underneath and the auditor must catch it — the
    expected spec is recomputed here from the declared axis entries via
    :func:`repro.sharding.specs.ambient_fit`, independent of what the
    wrapped function resolves.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import compat
    from repro.sharding import specs as sharding_specs

    orig = sharding_specs.maybe_wsc

    def checked_wsc(x, *spec):
        y = orig(x, *spec)
        am = compat.get_abstract_mesh()
        if not am.axis_names:
            return y
        expected = P(*(sharding_specs.ambient_fit(d, e)
                       for d, e in zip(x.shape, spec)))
        exp_sharding = NamedSharding(mesh, expected)
        rec = CheckRecord(label=_call_site(), shape=tuple(x.shape),
                          declared=str(spec), expected=str(expected),
                          scenario=scenario)
        report.records.append(rec)

        def verdict(actual):
            rec.actual = str(actual)
            try:
                rec.ok = bool(actual.is_equivalent_to(exp_sharding,
                                                      len(rec.shape)))
            except (TypeError, AttributeError):
                rec.ok = rec.actual == str(exp_sharding)

        if compat.is_tracer(y):
            jax.debug.inspect_array_sharding(y, callback=verdict)
        else:
            verdict(y.sharding)
        return y

    sharding_specs.maybe_wsc = checked_wsc
    try:
        yield report
    finally:
        sharding_specs.maybe_wsc = orig


def check_placement(report: AuditReport, label: str, arr, mesh,
                    pspec, scenario: str = "") -> None:
    """Record a concrete array's placement vs a declared PartitionSpec."""
    from jax.sharding import NamedSharding

    exp = NamedSharding(mesh, pspec)
    rec = CheckRecord(label=label, shape=tuple(arr.shape),
                      declared=str(pspec), expected=str(pspec),
                      scenario=scenario)
    rec.actual = str(arr.sharding)
    try:
        rec.ok = bool(arr.sharding.is_equivalent_to(exp, arr.ndim))
    except (TypeError, AttributeError):
        rec.ok = rec.actual == str(exp)
    report.records.append(rec)


# ------------------------------------------------------------- scenarios

def _build_case(scale: str, backend: str):
    """Two-layer catwalk net whose dims divide the (2, 4) mesh."""
    from repro.core import layer as layer_mod
    from repro.core import network

    if scale == "full":
        l0 = layer_mod.TNNLayer(n_columns=64, rf_size=8, n_neurons=8,
                                threshold=4, t_steps=16, dendrite="catwalk",
                                k=2, backend=backend)
        l1 = layer_mod.TNNLayer(n_columns=16, rf_size=32, n_neurons=8,
                                threshold=4, t_steps=16, dendrite="catwalk",
                                k=2, backend=backend)
        batch = 32
    else:
        l0 = layer_mod.TNNLayer(n_columns=8, rf_size=4, n_neurons=4,
                                threshold=4, t_steps=16, dendrite="catwalk",
                                k=2, backend=backend)
        l1 = layer_mod.TNNLayer(n_columns=4, rf_size=8, n_neurons=4,
                                threshold=4, t_steps=16, dendrite="catwalk",
                                k=2, backend=backend)
        batch = 8
    return network.make_network([l0, l1]), batch


def _make_inputs(cfg, batch: int, mesh):
    import jax
    import numpy as np

    from repro.core import coding, network

    key = jax.random.PRNGKey(0)
    params = network.init_network(key, cfg)
    rng = np.random.default_rng(0)
    v = rng.integers(0, cfg.layers[0].t_steps,
                     size=(batch, cfg.n_inputs)).astype(np.int32)
    # sprinkle silent lines: the engines must keep layouts on sparse
    # volleys too (NO_SPIKE rows are the serve path's padding)
    v[rng.random(v.shape) < 0.5] = int(coding.NO_SPIKE)
    placed_params = tuple(
        jax.device_put(w, s) for w, s in zip(
            params, network.param_shardings(cfg, mesh)))
    placed_v = jax.device_put(v, network.data_sharding(cfg, mesh, batch))
    return placed_params, placed_v


def _run_scenario(name: str, mesh, report: AuditReport,
                  scale: str) -> None:
    import jax

    from repro.core import network
    from repro.sharding import compat
    from repro.sharding import specs as sharding_specs

    backend = "pallas" if name == "pallas" else "closed_form"
    cfg, batch = _build_case(scale, backend)
    params, volleys = _make_inputs(cfg, batch, mesh)

    with compat.set_mesh(mesh), audit_scope(mesh, report, scenario=name):
        if name in ("forward", "pallas"):
            fn = jax.jit(lambda p, v: network.forward(p, v, cfg).out)
            out = fn(params, volleys)
        elif name == "pipelined":
            fn = jax.jit(
                lambda p, v: network.forward(p, v, cfg,
                                             microbatches=2).out)
            out = fn(params, volleys)
        elif name == "step":
            fn = jax.jit(lambda p, v: network.step(p, v, cfg)[:2])
            new_params, out = fn(params, volleys)
        else:
            raise ValueError(f"unknown scenario {name!r}")
        jax.block_until_ready(out)

    # output placements, checked on the concrete results against the
    # externally-declared twins of the in-jit rules
    last = cfg.layers[-1]
    check_placement(
        report, "network output (B, C, Q) [tnn stage rule]", out, mesh,
        _out_pspec(mesh, out.shape), scenario=name)
    if name == "step":
        for i, (w, lc) in enumerate(zip(new_params, cfg.layers)):
            check_placement(
                report, f"post-STDP weights layer {i} [tnn_param_pspec]",
                w, mesh,
                sharding_specs.tnn_param_pspec(mesh, lc.n_columns),
                scenario=name)
    del last


def _out_pspec(mesh, shape):
    """Declared rule for the post-WTA (B, C, Q) output volley."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import specs as sharding_specs

    dp, col = sharding_specs.tnn_stage_axes()
    return P(sharding_specs._fit(mesh, shape[0],
                                 sharding_specs.dp_axes(mesh)),
             sharding_specs._fit(mesh, shape[1], col),
             None)


def run_audit(mesh=None, scenarios: Sequence[str] = DEFAULT_SCENARIOS,
              scale: str = "smoke", n_data: int = 2,
              n_column: int = 4) -> AuditReport:
    """Run the layout audit; returns the report (caller decides to fail).

    ``mesh=None`` builds ``tnn_mesh(n_column, n_data)`` from the visible
    devices (the CLI forces 8 host devices for itself; tests inherit the
    shard-suite's subprocess XLA_FLAGS).
    """
    from repro.sharding import specs as sharding_specs

    if mesh is None:
        mesh = sharding_specs.tnn_mesh(n_column=n_column, n_data=n_data)
    report = AuditReport()
    for name in scenarios:
        _run_scenario(name, mesh, report, scale)
    return report


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.layout_audit",
        description="Diff actual vs declared shardings on the host mesh "
                    "(DESIGN.md §7.2)")
    ap.add_argument("--scale", choices=("smoke", "full"), default="smoke")
    ap.add_argument("--n-data", type=int, default=2)
    ap.add_argument("--n-column", type=int, default=4)
    ap.add_argument("--host-devices", type=int, default=8,
                    help="forced host device count (before jax init)")
    ap.add_argument("--scenarios", nargs="*", default=list(DEFAULT_SCENARIOS))
    args = ap.parse_args(argv)

    import os
    if "jax" not in sys.modules:
        # must precede jax init; raw write is the only option this
        # early  # repro-lint: allow[raw-env]
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.host_devices}")
    import jax
    need = args.n_data * args.n_column
    if len(jax.devices()) < need:
        print(f"layout-audit: need {need} devices for a "
              f"({args.n_data}, {args.n_column}) mesh, have "
              f"{len(jax.devices())} (is XLA_FLAGS set before jax init?)",
              file=sys.stderr)
        return 2

    report = run_audit(scenarios=tuple(args.scenarios), scale=args.scale,
                       n_data=args.n_data, n_column=args.n_column)
    print(report.render())
    if not report.checked:
        print("layout-audit: NO checks fired — instrumentation broke",
              file=sys.stderr)
        return 2
    if report.mismatches:
        print(f"layout-audit: FAILED ({len(report.mismatches)} layout "
              "mismatch(es), see MISMATCH rows above)", file=sys.stderr)
        return 1
    print("layout-audit: all layouts match the declared rules")
    return 0


if __name__ == "__main__":
    sys.exit(main())
