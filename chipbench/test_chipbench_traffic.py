"""The benchmark's traffic: the numpy GRF copy against the program's encoder,
one seed giving one schedule and one pool, the unfolded layout and the
scenes' frames. CPU only."""

import json

import numpy as np
import pytest

from harness import grf, spec, traffic


@pytest.mark.parametrize("cutoff,fields,t_max", [(0.05, 8, 16), (0.5, 8, 16),
                                                (0.9, 8, 16), (0.5, 2, 8)])
def test_grf_copy_equals_program_encoder(cutoff, fields, t_max):
    import jax.numpy as jnp

    from repro.core import coding

    feats = np.random.default_rng(7).random((96, 64), dtype=np.float32)
    feats[:, :8] = np.round(feats[:, :8])    # a sensor's 0 and 1 readings
    want = np.asarray(coding.grf_encode(jnp.asarray(feats), fields, t_max,
                                        cutoff=cutoff))
    got = grf.grf_encode(feats, fields, t_max, cutoff=cutoff)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def _traffic(name):
    with open(spec.BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _config(name):
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["frames-closed-256", "frames-poisson-ff",
                                 "frames-poisson-learn"])
def test_one_seed_one_schedule(mix):
    tr = _traffic(mix)
    seed = 2 ** 31 + 977            # larger than 32 signed bits hold
    a, b = traffic.schedule(tr, 10.0, seed), traffic.schedule(tr, 10.0, seed)
    np.testing.assert_array_equal(a.lengths, b.lengths)
    np.testing.assert_array_equal(a.offsets, b.offsets)
    other = traffic.schedule(tr, 10.0, seed + 1)
    # another seed: the same multiset of lengths (and of gaps), in another order
    assert not np.array_equal(a.lengths, other.lengths)
    np.testing.assert_array_equal(np.sort(a.lengths), np.sort(other.lengths))
    assert a.lengths.min() >= tr["length_min"]
    assert a.lengths.max() <= tr["length_max"]
    # every request stays inside one scene of the pool
    first = a.offsets // traffic.SCENE_FRAMES
    last = (a.offsets + a.lengths - 1) // traffic.SCENE_FRAMES
    np.testing.assert_array_equal(first, last)
    if tr["loop"] == "open":
        np.testing.assert_array_equal(a.due, b.due)
        np.testing.assert_allclose(np.sort(np.diff(a.due, prepend=0.0)),
                                   np.sort(np.diff(other.due, prepend=0.0)))
        assert 0 < a.due[0] and a.due[-1] <= 10.0
        assert np.all(np.diff(a.due) >= 0)
        assert len(a.due) == round(tr["rate_per_s"] * 10.0)
    else:
        assert a.due is None and a.clients == tr["clients"]


def test_one_seed_one_pool():
    cfg = _config("tnn-proto-2l")
    p1 = traffic.volley_pool(cfg, 123456789012)
    p2 = traffic.volley_pool(cfg, 123456789012)
    np.testing.assert_array_equal(p1, p2)
    lay = cfg["layers"][0]
    assert p1.shape == (traffic.POOL_VOLLEYS,
                        lay["n_columns"] * lay["rf_size"])
    assert p1.dtype == np.int32
    assert not np.array_equal(p1, traffic.volley_pool(cfg, 123456789013))
    # on/off pairs: every pixel but the grey edges of strokes fires one line
    assert 0.5 < float(np.mean(p1 < cfg["t_input"])) < 0.62


def test_unfold_reads_each_columns_patch():
    cfg = _config("tnn-proto-2l")
    side, patch, f = cfg["image_side"], cfg["patch_side"], cfg["n_fields"]
    idx = traffic.unfold_index(cfg).reshape(side - patch + 1,
                                            side - patch + 1, patch, patch, f)
    lay = cfg["layers"][0]
    assert idx.size == lay["n_columns"] * lay["rf_size"]
    # column (2, 5): pixel (2 + 3, 5 + 1), field 1
    assert idx[2, 5, 3, 1, 1] == ((2 + 3) * side + (5 + 1)) * f + 1
    # every line of the encoded image is read by some column
    assert np.array_equal(np.unique(idx), np.arange(side * side * f))


def test_frames_of_a_scene_are_alike():
    cfg = _config("tnn-proto-2l")
    img = traffic.frames(cfg, 4 * traffic.SCENE_FRAMES, traffic.seed_rng(9, 1))
    assert img.min() >= 0 and img.max() <= 1
    # strokes cover about a fifth of the frame, as handwriting does
    assert 0.1 < float(np.mean(img > 0.5)) < 0.3
    s = traffic.SCENE_FRAMES
    within = np.mean(np.abs(img[1:s] - img[:s - 1]))
    across = np.mean(np.abs(img[s:2 * s] - img[:s]))
    assert within < across / 2


def test_stream_wraps_the_pool():
    pool = np.arange(10, dtype=np.int32)[:, None] * np.ones((1, 3), np.int32)
    np.testing.assert_array_equal(traffic.stream(pool, 8, 4)[:, 0],
                                  [8, 9, 0, 1])
