"""The LM system (``systems/lm.py``) end to end through ``runner.run`` on the
CPU, at a tiny configuration of DeepSeek-V2-Lite's shape
(``testdata/lm/deepseek-v2-lite-tiny.json``: every width cut, a share of 4
of 16 routed experts from expert 4): a sound run reads correct, and faults
planted in the program read not correct."""

import dataclasses
import json
import time

import jax
import pytest

from harness import runner, spec

TINY = spec.BENCH_DIR / "testdata" / "lm" / "deepseek-v2-lite-tiny.json"
SECONDS = 1.5
E2E = [{"name": "request_latency_p95_ms", "unit": "ms"},
       {"name": "setup_s", "unit": "s"}]
TRAFFIC = {"loop": "open", "rate_per_s": 12, "length_min": 2,
           "length_max": 4}


@pytest.fixture(scope="module")
def cell():
    config = json.loads(TINY.read_text())
    return spec.Cell(name="dsv2l-tiny", chips=1, config=config,
                     traffic=TRAFFIC, end_to_end=E2E, per_layer=[],
                     system=spec.load_system(config["system"]))


def _run(cell, engine=None, seed=2 ** 32 + 41):
    return runner.run(cell, seed, SECONDS, False, time.perf_counter(),
                      engine=engine, log=lambda msg: None)


def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["compared"]) == {"lost_requests", "logit_err_max",
                                    "lse_err_max", "argmax_gap_max",
                                    "logit_err_mean"}
    assert res["compared"]["lost_requests"]["value"] == 0
    assert set(res["metrics"]) == {"request_latency_p95_ms", "setup_s"}


def _faulty(cell, fault):
    """An engine factory with one fault planted in the program."""
    lm = cell.system

    def factory(params, config, name):
        mc = lm.model_config(config)
        if fault == "renormalised_gates":
            mc = dataclasses.replace(mc, moe=dataclasses.replace(
                mc.moe, norm_topk=True))
        if fault == "dropped_expert":
            # the program computes nothing for held expert 1's assignments
            moe = dict(params["layers"]["moe"])
            moe["w_down"] = moe["w_down"].at[:, 1].set(0)
            params = dict(params, layers=dict(params["layers"], moe=moe))
        return lm.Program(params, mc, config)

    return factory


@pytest.mark.parametrize("fault", ["dropped_expert", "renormalised_gates",
                                   "no_kv_layernorm"])
def test_planted_fault_reads_not_correct(cell, fault, monkeypatch):
    if fault == "no_kv_layernorm":
        from repro.models import attention as A
        project = A.mla_project

        def without_norm(p, x, cfg, positions):
            q_nope, q_rope, _, k_rope = project(p, x, cfg, positions)
            return q_nope, q_rope, x @ p["w_dkv"], k_rope

        monkeypatch.setattr(A, "mla_project", without_norm)
    res = _run(cell, engine=_faulty(cell, fault))
    assert not res["correct"], res["compared"]


def test_control_reads_not_correct(cell):
    res = _run(cell, engine=lambda p, cfg, name:
               cell.system.control_engine(p, cfg))
    assert not res["correct"], res["compared"]


def test_reference_blocks_give_the_full_forward(cell):
    """The benchmark's blockwise reference equals the program's plain
    float32 reference (``models/reference.py``) on one sequence."""
    import numpy as np

    from repro.models import reference
    from systems import _lm_reference

    config = cell.config
    lm = cell.system
    params = jax.device_get(lm.init_params(jax.random.PRNGKey(3), config))
    seq = np.random.default_rng(0).integers(
        0, config["vocab_size"], 50).astype(np.int32)
    full = np.asarray(reference.forward(params, lm.model_config(config), seq,
                                        e_lo=config["e_lo"]))
    ref = _lm_reference.Reference(params, config)
    targets = np.arange(50) * 7 % config["vocab_size"]
    kv, got = ref.empty(), []
    for s, n in ((0, 24), (24, 26)):            # two blocks, padded to 32
        chunk, tgt = np.zeros((2, 32), np.int32)
        chunk[:n], tgt[:n] = seq[s:s + n], targets[s:s + n]
        kv, st = ref.run(kv, chunk, s, n, tgt)
        got.append(np.asarray(st)[:n])
    want = np.stack([full[np.arange(50), targets],
                     np.asarray(jax.nn.logsumexp(full, -1)), full.max(-1),
                     full.std(-1)], -1)
    np.testing.assert_allclose(np.concatenate(got), want, rtol=1e-4,
                               atol=1e-4)


def test_launch_times_split_a_steps_device_time():
    """Device time inside each ``lm.step`` span goes to its launches:
    decode-only steps give the decode time, the rest less that time per
    prefill chunk (``systems/_lm_spans.py``)."""
    from harness import trace
    from systems import _lm_spans

    ms = 1e6
    host, ops = [], []
    # (start, decode-only device ms or (prefill chunks, busy ms))
    for k, (chunks, busy) in enumerate([(0, 10), (1, 30), (0, 12),
                                        (2, 51)]):
        s = k * 100 * ms
        host += [("chipbench.step", s, 90 * ms, ""),
                 ("lm.step", s + ms, 88 * ms, "")]
        host += [("lm.prefill", s + 2 * ms + i, 0.5 * ms, "")
                 for i in range(chunks)]
        host += [("lm.decode", s + 3 * ms, 0.5 * ms, "")]
        # two ops per step, with a gap between them
        ops += [("fusion", s + 4 * ms, busy / 2 * ms, "%fusion"),
                ("fusion.1", s + 5 * ms + busy / 2 * ms, busy / 2 * ms,
                 "%fusion.1")]
    tr = trace.Trace([ops], host)
    assert _lm_spans.decode_ms(tr) == pytest.approx(11.0)
    assert _lm_spans.prefill_ms(tr) == pytest.approx((19 + 20) / 2)
    assert _lm_spans.decode_ms(trace.Trace([ops], [])) is None


def test_decode_work_counts_the_published_shapes():
    """The decode step's least bytes at the cell's size: 5.8 GB of weights
    (3.11e9 parameters in bf16, less the embedding table's 0.42 GB) plus
    the latent cache, 31,104 bytes a position over 27 layers."""
    from systems import _lm_work

    config = json.loads((spec.BENCH_DIR / "configs" /
                         "deepseek-v2-lite-ep8.json").read_text())
    w = _lm_work.weight_bytes(config)
    assert 5.75e9 < w < 5.85e9
    flops, nbytes = _lm_work.decode_work(config, [2048] * 64)
    assert nbytes - w == pytest.approx(
        27 * (2048 * 64 + 64) * 576 * 2 + 64 * 2048 * 2)
    assert 1e11 < flops < 1e12            # bound by bytes, not operations
