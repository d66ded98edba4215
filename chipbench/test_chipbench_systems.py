"""A configuration names its system, and the harness finds that system's
code by name: a new system is new files only. A toy system that lives under
``testdata/systems/`` alone runs end to end through ``runner.run`` on the
CPU; its control and a fault planted in its engine read not correct."""

import pathlib
import time

import pytest

from harness import runner, spec

BENCH = spec.load_benchmark()
TOY_DIRS = (spec.BENCH_DIR / "testdata" / "systems",)
CONTRACT = ("pool", "payload", "init_params", "engine", "control_engine",
            "stateful", "compare")
TOY_BENCH = {
    "configs": [{"name": "rowsum",
                 "file": "chipbench/testdata/systems/rowsum.json"}],
    "workloads": [
        {"name": "rowsum-closed", "config": "rowsum",
         "traffic": "frames-closed-256", "chips": 1},
        {"name": "rowsum-open", "config": "rowsum",
         "traffic": "frames-poisson-ff", "chips": 1}],
    "end_to_end": [
        {"name": "volleys_per_s", "unit": "volleys/s",
         "workloads": ["rowsum-closed"]},
        {"name": "request_latency_p95_ms", "unit": "ms",
         "workloads": ["rowsum-open"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": []}
SECONDS = 1.0


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configurations_resolve_to_the_tnn_system(config):
    cell = next(w["name"] for w in BENCH["workloads"]
                if w["config"] == config)
    path = pathlib.Path(spec.find_cell(BENCH, cell).system.__file__)
    assert path == spec.BENCH_DIR / "systems" / "tnn.py"


@pytest.mark.parametrize("path", sorted(
    (spec.BENCH_DIR / "systems").glob("[!_]*.py")) + [TOY_DIRS[0] /
                                                      "rowsum.py"],
    ids=lambda p: p.stem)
def test_system_modules_give_the_contract(path):
    module = spec.load_system(path.stem, [path.parent])
    for name in CONTRACT:
        assert callable(getattr(module, name)), name


def test_a_system_is_found_only_on_the_search_path():
    with pytest.raises(LookupError):
        spec.find_cell(TOY_BENCH, "rowsum-closed")
    with pytest.raises(LookupError):
        spec.load_system("no_such_system")


def _run(cell_name, engine=None, seed=2 ** 32 + 17):
    cell = spec.find_cell(TOY_BENCH, cell_name, system_dirs=TOY_DIRS)
    return cell, runner.run(cell, seed, SECONDS, False, time.perf_counter(),
                            engine=engine, log=lambda msg: None)


@pytest.mark.parametrize("cell", ["rowsum-closed", "rowsum-open"])
def test_toy_system_runs_correct(cell):
    c, res = _run(cell)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["compared"]) == {"mismatched_rows", "lost_requests"}
    assert set(res["metrics"]) == {m["name"] for m in c.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_toy_control_fails():
    c = spec.find_cell(TOY_BENCH, "rowsum-closed", system_dirs=TOY_DIRS)
    _, res = _run("rowsum-closed", engine=lambda p, cfg, name:
                  c.system.control_engine(p, cfg))
    assert not res["correct"]
    assert res["compared"]["mismatched_rows"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer_altered", "request_lost"])
def test_toy_planted_fault_is_caught(fault):
    c = spec.find_cell(TOY_BENCH, "rowsum-closed", system_dirs=TOY_DIRS)

    class Broken(c.system.Engine):
        def step(self):
            retired = super().step()
            if fault == "answer_altered" and self.n_volleys > 100:
                for h in retired[:1]:
                    h.outputs[-1] += 1
            elif fault == "request_lost":
                retired = [h for h in retired if h.req_id != 300]
            return retired

    _, res = _run("rowsum-closed", engine=lambda p, cfg, name:
                  Broken(p, cfg))
    assert not res["correct"], res["compared"]
    number = ("mismatched_rows" if fault == "answer_altered"
              else "lost_requests")
    assert res["compared"][number]["value"] > 0
