"""The readers of the program's spans (``harness/spans.py``, ``idle_*_ms``,
``snapshot_stall_ms``) on synthetic traces with known answers, and on three
steps of ``ff-grf-sat`` recorded on a TPU v5e with the spans in place
(``testdata/``)."""

import json

import numpy as np
import pytest

from harness import runner, spans, spec, trace

FIXTURE = spec.BENCH_DIR / "testdata" / "trace_ff_grf_sat_3steps_spans.json"
MS = 1e6
IDLE = ["idle_host_ms", "idle_measure_ms", "idle_transfer_ms",
        "idle_outside_ms"]


def _step(t0, phases):
    """A ``tnn.step`` span from ``t0`` (ms) with its phases laid end to
    end: [(name, ms), ...], then 5 ms of the step's own lines."""
    out, t = [], t0
    for name, ms in phases:
        out.append((name, t * MS, ms * MS, ""))
        t += ms
    return [("tnn.step", t0 * MS, (t + 5 - t0) * MS, "")] + out


def _synthetic():
    """Two benchmark steps, 0-100 and 110-200 ms, a submit between them.
    Device ops 52-78 and 136-165 ms, so it idles 0-52, 78-136, 165-200."""
    host = [("chipbench.step", 0.0, 100 * MS, ""),
            ("chipbench.submit", 100 * MS, 10 * MS, ""),
            ("chipbench.step", 110 * MS, 90 * MS, "")]
    host += _step(5, [("tnn.admit", 5), ("tnn.batch", 10),
                      ("tnn.measure", 20), ("tnn.resolve", 2),
                      ("tnn.h2d", 8), ("tnn.dispatch", 5),
                      ("tnn.fetch", 25), ("tnn.retire", 6),
                      ("tnn.snapshot", 4)])                     # to 95 ms
    host += _step(112, [("tnn.admit", 2), ("tnn.batch", 6),
                        ("tnn.measure", 10), ("tnn.resolve", 1),
                        ("tnn.h2d", 4), ("tnn.dispatch", 3),
                        ("tnn.fetch", 32), ("tnn.retire", 10),
                        ("tnn.snapshot", 11)])                  # to 196 ms
    devices = [[("k", 52 * MS, 26 * MS, "k"), ("k", 136 * MS, 29 * MS, "k")]]
    return trace.Trace(devices=devices, host=host)


def _view(tr, cell="ff-grf-sat"):
    return runner.RunView(cell=spec.find_cell(spec.load_benchmark(), cell),
                          setup_s=1.0, t0=0.0, t_end=1.0, requests=[],
                          steps=np.zeros((0, 3)), slice=None, trace=tr,
                          peak={})


def test_idle_split_on_synthetic_spans():
    # idle under the steps: 5-52, 78-95, 112-136, 165-196 (119 ms), of
    # which measure 20-40 and 120-130, transfer 42-50, 78-80, 131-135 and
    # 165-170; outside them 0-5, 95-112, 196-200
    split = spans.idle_split(_synthetic())
    assert split == pytest.approx({"measure": 30 / 2, "transfer": 19 / 2,
                                   "host": 70 / 2, "outside": 26 / 2})


@pytest.mark.parametrize("variant", ["sat", "lat"])
def test_the_idle_readers_partition_the_idle_time(variant):
    tr = _synthetic()
    view = _view(tr)
    got = [spec.load_reader(f"{name}.{variant}")(view) for name in IDLE]
    a, b, n = trace.window(tr)
    idle_ms = (b - a - trace.busy_ns(tr, a, b)) / MS / n
    assert sum(got) == pytest.approx(idle_ms) == 145 / 2
    assert spec.load_reader(f"device_idle_pct.{variant}")(view) / 100 \
        * (b - a) / MS / n == pytest.approx(sum(got))


def test_a_trace_without_the_programs_spans_reads_none():
    tr = _synthetic()
    tr.host = [e for e in tr.host if not e[0].startswith("tnn.")]
    view = _view(tr)
    for name in IDLE + ["snapshot_stall_ms"]:
        assert spec.load_reader(name)(view) is None
    assert spec.load_reader("idle_host_ms")(_view(None)) is None


def test_snapshot_stall_averages_only_snapshot_spans():
    view = _view(_synthetic(), "learn-grf-lat")
    assert spec.load_reader("snapshot_stall_ms")(view) == pytest.approx(
        (4 + 11) / 2)
    tr = _synthetic()
    tr.host = [e for e in tr.host if e[0] != "tnn.snapshot"]
    assert spec.load_reader("snapshot_stall_ms")(_view(tr)) is None


def test_the_readers_on_a_chip_trace():
    with open(FIXTURE) as f:
        tr = trace.Trace.from_json(json.load(f))
    names = [n for n, _, _, _ in tr.host]
    # every phase of every step was kept, however short
    assert names.count("tnn.step") == 3
    for phase in ["tnn.admit", "tnn.batch", "tnn.measure", "tnn.resolve",
                  "tnn.h2d", "tnn.dispatch", "tnn.fetch", "tnn.retire"]:
        assert names.count(phase) == 3
    view = _view(tr)
    got = {name: spec.load_reader(f"{name}.sat")(view) for name in IDLE}
    assert got == pytest.approx({"idle_host_ms": 5.722437,
                                 "idle_measure_ms": 9.266453,
                                 "idle_transfer_ms": 4.472082,
                                 "idle_outside_ms": 4.127420}, rel=1e-6)
    a, b, n = trace.window(tr)
    assert sum(got.values()) == pytest.approx(
        spec.load_reader("device_idle_pct.sat")(view) / 100 * (b - a) / MS
        / n)
    # the kernel keeps the name its reader matches
    assert spec.load_reader("rnl_kernel_ms")(view) == pytest.approx(
        38.113394, rel=1e-6)
    # the feedforward cell takes no snapshots
    assert spec.load_reader("snapshot_stall_ms")(view) is None
