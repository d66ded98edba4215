"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
a configuration names the system it runs on (its ``"system"`` key, ``"tnn"``
when it has none). Each of these, and each per-layer metric, lives in a file
of its own:

  configs/<file named by the configuration entry>   the deployment as it is run
  systems/<system>.py                                the system's pool, engine,
                                                     reference and comparison
  traffic/<traffic>.json                             the mix's parameters
  metrics/<metric>.py                                a per-layer metric's reader

so a later change adds a system, a configuration, a mix or a metric by adding
files and entries, never by editing a file that is already there. A metric
named ``<base>.<variant>`` (``step_ms.sat``) is read by
``metrics/<base>.<variant>.py`` when that file exists, else by
``metrics/<base>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import types
from typing import Callable, Dict, List, Optional, Sequence

#: the benchmark's own directory (``chipbench/``) and the checkout's root
BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
#: where ``find_cell`` looks for ``<system>.py``, in order
SYSTEM_DIRS = (BENCH_DIR / "systems",)
#: the system of a configuration that names none
DEFAULT_SYSTEM = "tnn"


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
    #: the configuration's system module (``systems/__init__.py``)
    system: types.ModuleType


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


def _load_module(path: pathlib.Path, prefix: str) -> types.ModuleType:
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    # a dataclass with string annotations looks its module up here
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_system(name: str, dirs: Sequence[pathlib.Path] = SYSTEM_DIRS
                ) -> types.ModuleType:
    """The module of the system called ``name``: the first ``<name>.py``
    in ``dirs``; LookupError if none."""
    for d in dirs:
        path = pathlib.Path(d) / f"{name}.py"
        if path.exists():
            return _load_module(path, "chipbench_system_")
    raise LookupError(f"no system {name!r} under {[str(d) for d in dirs]}")


def find_cell(bench: Dict, name: str,
              system_dirs: Sequence[pathlib.Path] = SYSTEM_DIRS) -> Cell:
    """The cell called ``name``, with its files loaded and its system found
    in ``system_dirs``; KeyError if there is no such cell."""
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
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                system=load_system(config.get("system", DEFAULT_SYSTEM),
                                   system_dirs))


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
    return _load_module(reader_path(metric), "chipbench_metric_").read
