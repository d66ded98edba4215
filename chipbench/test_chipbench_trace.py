"""Trace reduction on a small recorded trace (three steps of ``ff-grf-sat``
on a TPU v5e, ``testdata/``) and on synthetic events with known answers."""

import json
import time

import numpy as np
import pytest

from harness import peaks, runner, spec, trace

FIXTURE = spec.BENCH_DIR / "testdata" / "trace_ff_grf_sat_3steps.json"


@pytest.fixture(scope="module")
def chip():
    with open(FIXTURE) as f:
        return trace.Trace.from_json(json.load(f))


def test_window_spans_the_step_spans(chip):
    a, b, n = trace.window(chip)
    steps = [(s, s + d) for name, s, d, _ in chip.host
             if name == "chipbench.step"]
    assert n == 3 == len(steps)
    assert (a, b) == (min(s for s, _ in steps), max(e for _, e in steps))


def _timeline(events, a, b):
    """Busy microseconds of [a, b] on a 1 us grid (independent of union)."""
    grid = np.zeros(int((b - a) // 1000) + 1, bool)
    for _, s, d, _ in events:
        lo, hi = max(s, a), min(s + d, b)
        if hi > lo:
            grid[int((lo - a) // 1000):int(np.ceil((hi - a) / 1000))] = True
    return grid


def test_busy_time_is_the_union_of_operations(chip):
    a, b, _ = trace.window(chip)
    busy = trace.busy_ns(chip, a, b)
    grid = _timeline(chip.devices[0], a, b)
    # each interval's two ends can each be off by one grid cell
    assert abs(busy / 1000 - grid.sum()) <= 2 * len(chip.devices[0])
    # the RNL kernel dominates, but most of the window is idle
    assert 0.2 < busy / (b - a) < 0.5


def test_kernel_time_and_its_readers(chip):
    a, b, n = trace.window(chip)
    want = sum(d for name, s, d, _ in chip.devices[0]
               if name.startswith("rnl_fire_times_layer.")
               and a <= s and s + d <= b)
    assert trace.kernel_ns(chip, r"^rnl_fire_times_layer(\.\d+)?$", a, b) \
        == want > 0
    cell = spec.find_cell(spec.load_benchmark(), "ff-grf-sat")
    view = runner.RunView(cell=cell, setup_s=1.0, t0=0.0, t_end=1.0,
                          requests=[], steps=np.zeros((0, 3)), slice=None,
                          trace=chip, peak=peaks.peak("TPU v5 lite"))
    kernel_ms = spec.load_reader("rnl_kernel_ms")(view)
    assert kernel_ms == pytest.approx(want / 1e6 / n)
    roof = spec.load_reader("rnl_roofline")(view)
    assert 0 < roof < 100
    # the cell's 22.4 MB a step over 819 GB/s against the measured
    # milliseconds (the fixture's kernel ran another network: arithmetic only)
    assert roof == pytest.approx(100 * 22_380_000 / 819e9 / (kernel_ms / 1e3))
    idle = spec.load_reader("device_idle_pct.sat")(view)
    assert idle == pytest.approx(100 * (1 - trace.busy_ns(chip, a, b)
                                        / (b - a)))


def test_breakdown_accounts_for_the_idle_time(chip):
    a, b, _ = trace.window(chip)
    ops = trace.op_totals(chip, a, b)
    assert ops[0][0] == "rnl_fire_times_layer.2" and len(ops) <= 10
    assert all(ops[i][1] >= ops[i + 1][1] for i in range(len(ops) - 1))
    idle = trace.idle_gaps(chip, a, b, top=100)
    total = sum(sec for _, sec in idle)
    assert total == pytest.approx((b - a - trace.busy_ns(chip, a, b)) / 1e9)
    names = [name for name, _ in idle]
    assert "$tnn_engine.py:687 _layer0_active" in names[:3]


def test_union_gaps_and_attribution_on_synthetic_events(monkeypatch):
    t = trace.Trace(
        devices=[[("k", 10.0, 10.0, "k"), ("k", 15.0, 10.0, "k"),
                  ("x", 40.0, 5.0, "x")]],
        host=[("chipbench.step", 0.0, 100.0, ""),
              ("outer", 20.0, 60.0, ""), ("inner", 30.0, 20.0, "")])
    assert trace.union([(10, 20), (15, 25), (40, 45)], 0, 100) == \
        [(10, 25), (40, 45)]
    assert trace.busy_ns(t, 0.0, 100.0) == 20.0
    assert trace.gaps(t, 0.0, 100.0) == [(0.0, 10.0), (25.0, 40.0),
                                         (45.0, 100.0)]
    assert trace.kernel_ns(t, "^k$", 0.0, 100.0) == 20.0
    monkeypatch.setattr(trace, "HOST_MIN_NS", 0.0)
    idle = dict(trace.idle_gaps(t, 0.0, 100.0))
    # each idle instant goes to the innermost span over it: 0-10 and 80-100
    # the step alone, 25-30 and 50-80 outer, 30-40 and 45-50 inner
    assert idle == pytest.approx({"chipbench.step": 30e-9, "outer": 35e-9,
                                  "inner": 15e-9})


def test_load_reads_host_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("chipbench.step"):
            f(jnp.ones(8)).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    t = trace.load(str(tmp_path))
    assert not any(t.devices)          # no TPU plane on a CPU
    a, b, n = trace.window(t)
    assert n == 3 and b - a >= 6e6
    assert trace.busy_ns(t, a, b) == 0.0
