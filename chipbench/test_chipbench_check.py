"""The check that decides ``correct``, driven through whole runs of the
harness at a small size on the CPU (the chip look of ``run.py`` skipped):
sound runs read correct; the control (the reference with bfloat16 weights in
the program's place) and faults planted in the program read not correct."""

import copy
import dataclasses
import math
import time

import jax.numpy as jnp
import pytest

from harness import runner, spec, system, traffic

NO_SPIKE = 2 ** 30
BENCH = spec.load_benchmark()
SECONDS = 0.6


@pytest.fixture(autouse=True)
def _small_pool(monkeypatch):
    """A pool of 256 frames (16 scenes), so the reference's pass is short."""
    monkeypatch.setattr(traffic, "POOL_VOLLEYS", 256)


def _small(cell_name: str, **config) -> spec.Cell:
    """The cell at its own widths with 8 slots, on the CPU's engine and at a
    CPU's load."""
    cell = spec.find_cell(BENCH, cell_name)
    cfg = copy.deepcopy(cell.config)
    cfg.update(n_slots=8, backend="closed_form", **config)
    if cfg.get("checkpoint_every"):
        cfg["checkpoint_every"] = 10
    tr = dict(cell.traffic)
    tr.update({"rate_per_s": 60} if tr["loop"] == "open" else {"clients": 16})
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def _recurrent(cell_name: str, **config) -> spec.Cell:
    """The cell with a recurrent layer 2, whose carry spans a stream's
    gamma cycles (the harness serves and checks such configurations too)."""
    cell = _small(cell_name, **config)
    cell.config["layers"][1]["recurrent"] = True
    return cell


def _run(cell, engine=system.ProgramEngine, seed=2 ** 31 + 5):
    return runner.run(cell, seed, SECONDS, False, time.perf_counter(),
                      engine=engine, log=lambda msg: None)


@pytest.mark.parametrize("cell", ["ff-grf-sat", "ff-grf-lat",
                                  "learn-grf-lat", "recurrent"])
def test_sound_run_is_correct(cell):
    small = _recurrent("learn-grf-lat") if cell == "recurrent" else _small(cell)
    res = _run(small)
    cell = small.name
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    for name, entry in res["compared"].items():
        assert entry["value"] <= entry["limit"], name
    names = {m["name"] for m in spec.find_cell(BENCH, cell).end_to_end}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())


def _control(weights, config, cell):
    return system.ControlEngine(weights, config, wdtype=jnp.bfloat16)


def test_control_learning_fails():
    res = _run(_small("learn-grf-lat"), engine=_control)
    assert not res["correct"]
    assert res["compared"]["weights_differing"]["value"] > 0


def test_control_feedforward_fails():
    res = _run(_small("ff-grf-sat"), engine=_control)
    assert not res["correct"]
    assert res["compared"]["mismatched_volleys"]["value"] > 0


def _plant(monkeypatch, fault, config):
    """Break the program's jitted step underneath the engine."""
    from repro.core import layer, network
    from repro.serve import tnn_engine

    if fault == "layer1_shifted":
        # one layer-1 column at the middle of the image fires a tick late;
        # one layer-2 column reads it alone
        first = config["layers"][0]
        mid = first["n_columns"] // 2
        orig_layer = layer.layer_forward

        def layer_forward(weights, volleys, cfg, carry=None):
            out, win = orig_layer(weights, volleys, cfg, carry)
            if (cfg.rf_size, cfg.n_neurons) == (first["rf_size"],
                                                first["n_neurons"]):
                col = out[:, mid]
                out = out.at[:, mid].set(
                    jnp.where(col < NO_SPIKE, col + 1, col))
            return out, win

        monkeypatch.setattr(layer, "layer_forward", layer_forward)
        return

    orig = tnn_engine.TNNEngine._forward_fn

    def forward_fn(self, net, learn=False):
        fn = orig(self, net, learn)

        def broken(p, v, c, *key):
            res = list(fn(p, v, c, *key))
            if fault == "answer_altered":
                res[0] = res[0].at[0, 0, 0].set(res[0][0, 0, 0] ^ 1)
            elif fault == "half_batch":
                half = v.shape[0] // 2
                if learn:   # STDP's mean taken over the first half alone
                    c_half = tuple(None if ci is None else ci[:half]
                                   for ci in c)
                    res[2] = network.step(p, v[:half], net, key=key[0],
                                          carry=c_half).params
                else:       # the second half's volleys never reach the net
                    v = v.at[half:].set(NO_SPIKE)
                    res = list(fn(p, v, c))
            elif fault == "state_unchanged":
                if learn:
                    res[2] = p          # weights not updated
                else:
                    res[1] = c          # recurrent carries not updated
            return tuple(res)

        return broken

    monkeypatch.setattr(tnn_engine.TNNEngine, "_forward_fn", forward_fn)


@pytest.mark.parametrize("cell,fault", [
    ("ff-grf-sat", "answer_altered"),
    ("ff-grf-sat", "half_batch"),
    ("ff-grf-sat", "layer1_shifted"),
    ("learn-grf-lat", "answer_altered"),
    ("learn-grf-lat", "half_batch"),
    ("learn-grf-lat", "state_unchanged"),
    ("learn-grf-lat", "layer1_shifted"),
    ("recurrent", "state_unchanged"),
])
def test_planted_fault_is_caught(monkeypatch, cell, fault):
    if cell == "recurrent":
        # learning off: the recurrent carry is the state left alone
        small = _recurrent("learn-grf-lat", learn=False)
        del small.config["check"]["limits"]["weights_differing"]
    else:
        small = _small(cell)
    _plant(monkeypatch, fault, small.config)
    res = _run(small)
    assert not res["correct"], res["compared"]
    if fault == "layer1_shifted" and not small.config["learn"]:
        # caught through layer 2's answers alone
        assert res["compared"]["mismatched_volleys"]["value"] > 0


def test_lost_request_counts_against_correct(monkeypatch):
    """A request that never comes back fails the run and misses the
    latency limit."""
    orig = system.ProgramEngine.step

    def step(self):
        # the fourth request after the warm-up batch (one per slot)
        return [h for h in orig(self) if h.req_id != 8 + 3]

    monkeypatch.setattr(system.ProgramEngine, "step", step)
    res = _run(_small("ff-grf-lat"))
    assert not res["correct"]
    assert res["compared"]["lost_requests"]["value"] == 1
    assert res["failed"] == 1 and math.isfinite(
        res["metrics"]["request_latency_p95_ms"]["value"])
