"""Engine resolution (``policy.resolve_engine``): the decision table over
backend name x platform x mesh, and the reproduced paper tables
(DESIGN.md §3.7).

The platform and the mesh are patched in process; real meshes are covered
by the subprocess suites (tests/test_sharding_tnn.py,
tests/test_sharding_pallas.py).
"""

import json
import types
from pathlib import Path

import jax
import pytest

from repro.core import policy
from repro.sharding import compat

REPO = Path(__file__).resolve().parent.parent
ARTIFACTS = REPO / "benchmarks" / "artifacts"

BACKENDS = ("auto", "scan", "closed_form", "event", "pallas",
            "pallas_compact")

#: column counts per mesh case, against a 4-wide ``column`` axis
MESHES = {"none": None, "tiling": (8, 4), "non-tiling": (8, 5)}


def _expected(backend, platform, mesh):
    """The decision table of DESIGN.md §3.7: an engine name, or
    ``ValueError`` where an explicit Pallas engine cannot run."""
    fits = mesh != "non-tiling"
    if backend == "auto":
        return "pallas" if platform == "tpu" and fits else "closed_form"
    if backend in ("pallas", "pallas_compact") and not fits:
        return ValueError
    return backend


@pytest.mark.parametrize("mesh", list(MESHES))
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_resolve_engine_decision_table(backend, platform, mesh,
                                       monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if mesh != "none":
        fake = types.SimpleNamespace(axis_names=("data", "column"),
                                     shape={"data": 2, "column": 4})
        monkeypatch.setattr(compat, "get_abstract_mesh", lambda: fake)
    assert policy.mesh_active() == (mesh != "none")
    counts = MESHES[mesh] or (8, 5)
    want = _expected(backend, platform, mesh)
    if want is ValueError:
        with pytest.raises(ValueError, match="backend='auto'"):
            policy.resolve_engine(backend, counts)
    else:
        assert policy.resolve_engine(backend, counts) == want


def test_resolve_explicit_backend_passes_through():
    for b in BACKENDS[1:]:
        assert policy.resolve_engine(b) == b
        assert policy.resolve_engine(b, 4) == b
    with pytest.raises(ValueError, match="unknown backend"):
        policy.resolve_engine("fastest")


def test_resolve_unknown_workload_stays_dense(monkeypatch):
    """Off a TPU ``auto`` is the closed form whatever the shape; under a
    mesh an unknown shape (a 2-D bank) cannot take the Pallas kernels, so
    ``auto`` stays dense even on a TPU and an explicit Pallas request
    raises."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert policy.resolve_engine("auto") == "closed_form"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert policy.resolve_engine("auto") == "pallas"     # no mesh
    fake = types.SimpleNamespace(axis_names=("data", "column"),
                                 shape={"data": 2, "column": 4})
    monkeypatch.setattr(compat, "get_abstract_mesh", lambda: fake)
    assert policy.resolve_engine("auto") == "closed_form"
    assert policy.resolve_engine("auto", ()) == "closed_form"
    with pytest.raises(ValueError, match="backend='auto'"):
        policy.resolve_engine("pallas")


# ------------------------------------------------- paper-table artifact

def _paper_tables_rows():
    with open(ARTIFACTS / "BENCH_paper_tables.json") as f:
        art = json.load(f)
    assert art["smoke"] is False, "committed table artifact must be full"
    return {r["name"]: r["us_per_call"] for r in art["results"]}


def test_committed_paper_tables_reproduce_headline_ratios():
    rows = _paper_tables_rows()
    assert rows["table1/ratio_area_n64"] == pytest.approx(1.39, abs=0.05)
    assert rows["table1/ratio_power_n64"] == pytest.approx(1.86, abs=0.07)
    # the full Table I stays tight on average, not just at the headline
    assert rows["table1/mean_abs_err"] < 5.0  # percent


def test_paper_tables_bench_matches_committed_artifact():
    """Re-running the table emitter reproduces the committed rows exactly
    (the model is analytic — any drift is a real fidelity change)."""
    from benchmarks import common as bench_common
    from benchmarks import paper_tables
    from repro.core import hwcost
    t1 = paper_tables.table1_pnr(hwcost.calibrated())
    bench_common.reset_results()  # drop the rows emit() buffered above
    rows = _paper_tables_rows()
    for n in (16, 32, 64):
        ar, pr = t1["ratios"][n]
        assert rows[f"table1/ratio_area_n{n}"] == pytest.approx(
            ar, abs=1e-3)
        assert rows[f"table1/ratio_power_n{n}"] == pytest.approx(
            pr, abs=1e-3)
    paper_tables.check_headline(t1["ratios"])
