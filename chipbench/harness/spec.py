"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each of these, and each per-layer metric, lives in a file of its own:

  configs/<file named by the configuration entry>   the deployment as it is run
  traffic/<traffic>.json                             the mix's parameters
  metrics/<metric>.py                                a per-layer metric's reader

so a later change adds a configuration, a mix or a metric by adding files and
entries, never by editing a file that is already there. A metric named
``<base>.<variant>`` (``step_ms.sat``) is read by ``metrics/<base>.<variant>.py``
when that file exists, else by ``metrics/<base>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

#: the benchmark's own directory (``chipbench/``) and the checkout's root
BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload entry with its configuration, traffic and metrics."""

    name: str
    chips: int
    config: Dict
    traffic: Dict
    #: end-to-end metric entries this cell reports (``--trace 0``)
    end_to_end: List[Dict]
    #: per-layer metric entries this cell reports (``--trace 1``)
    per_layer: List[Dict]


def load_benchmark(path: Optional[pathlib.Path] = None) -> Dict:
    """The parsed ``BENCHMARK.json`` at the root of the checkout."""
    path = path or ROOT / "BENCHMARK.json"
    with open(path) as f:
        return json.load(f)


def _load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: Dict, name: str) -> Cell:
    """The cell called ``name``, with its files loaded; KeyError if none."""
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; have {sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(ROOT / configs[w["config"]]["file"])
    traffic = _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a workloads key is reported wherever the
    # end-to-end metric it moves is
    per_layer = [m for m in bench["per_layer"]
                 if (_reports(m, name) if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def reader_path(metric: str) -> pathlib.Path:
    """The reader file of a per-layer metric (see the module docstring)."""
    for stem in (metric, metric.split(".")[0]):
        path = BENCH_DIR / "metrics" / f"{stem}.py"
        if path.exists():
            return path
    raise LookupError(f"no reader for metric {metric!r} under "
                      f"{BENCH_DIR / 'metrics'}")


def load_reader(metric: str) -> Callable:
    """The ``read(run)`` function of a per-layer metric's reader file."""
    path = reader_path(metric)
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
