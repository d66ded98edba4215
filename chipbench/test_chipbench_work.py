"""The yardstick's arithmetic: the peaks table and the RNL ops and bytes."""

import json

import pytest

from harness import peaks, spec, work


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_v5e_peaks():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def _proto():
    with open(spec.BENCH_DIR / "configs" / "tnn-proto-2l.json") as f:
        return json.load(f)


def test_proto_2l_ops_and_bytes_match_hand_counts():
    # layer 1: C=625, B=128 slots, Q=12, R=32, T=16
    # layer 2: C=625, B=128, Q=10, R=12, T=16
    (ops1, bytes1), (ops2, bytes2) = work.layer_calls(_proto())
    assert ops1 == 2 * 625 * 128 * 12 * 32 * 16 == 983_040_000
    assert bytes1 == 4 * (625 * 128 * 32 + 625 * 12 * 32 + 625 * 128 * 12)
    assert bytes1 == 15_040_000
    assert ops2 == 2 * 625 * 128 * 10 * 12 * 16 == 307_200_000
    assert bytes2 == 4 * (625 * 128 * 12 + 625 * 10 * 12 + 625 * 128 * 10)
    assert bytes2 == 7_340_000
    # one volley through both layers: about 10.1 M operations
    assert work.ops_per_volley(_proto()) == 7_680_000 + 2_400_000


def test_recurrent_layer_counts_its_carry_lines():
    cfg = _proto()
    cfg["layers"][1]["recurrent"] = True
    assert [work.rf_total(lay) for lay in cfg["layers"]] == [32, 22]
    _, (ops2, _) = work.layer_calls(cfg)
    assert ops2 == 2 * 625 * 128 * 10 * 22 * 16


def test_least_time_takes_the_larger_bound_per_call():
    peak = peaks.peak("TPU v5 lite")
    calls = work.layer_calls(_proto())
    least = work.least_seconds(calls, peak)
    # both calls are bound by bytes: 18.4 us + 9.0 us
    assert least == pytest.approx((15_040_000 + 7_340_000) / 819e9)
    assert least > sum(ops for ops, _ in calls) / 393e12
