"""Neuron-bank backend dispatch + batched TNN layer/network subsystem."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coding, column, layer, network, neuron, policy, stdp

BACKENDS = ("scan", "closed_form", "pallas")
DENDRITES = ("pc_conventional", "pc_compact", "sorting_pc", "catwalk")


def _rand_volleys(key, shape, t_max, p_silent=0.3):
    kt, ks = jax.random.split(key)
    t = jax.random.randint(kt, shape, 0, t_max)
    silent = jax.random.bernoulli(ks, p_silent, shape)
    return jnp.where(silent, coding.NO_SPIKE, t)


# ------------------------------------------------------- fire_times_bank
@pytest.mark.parametrize("dendrite", DENDRITES)
@pytest.mark.parametrize("bsz,q,n", [(1, 1, 8), (5, 7, 16), (17, 9, 24)])
def test_fire_times_bank_backends_agree(dendrite, bsz, q, n):
    """All engines produce bit-identical fire times on random volleys."""
    cfg = neuron.NeuronConfig(n_inputs=n, threshold=9, t_steps=24,
                              dendrite=dendrite, k=2)
    times = _rand_volleys(jax.random.PRNGKey(bsz * 100 + n), (bsz, n), 30)
    w = jax.random.randint(jax.random.PRNGKey(q), (q, n), 0, 8)
    outs = [np.asarray(neuron.fire_times_bank(times, w, cfg, backend=b))
            for b in BACKENDS]
    assert outs[0].shape == (bsz, q)
    for b, got in zip(BACKENDS[1:], outs[1:]):
        np.testing.assert_array_equal(outs[0], got, err_msg=b)


@pytest.mark.parametrize("dendrite", DENDRITES)
def test_fire_times_bank_column_stack_agrees(dendrite):
    """3-D (C, B, n) dispatch matches per-column 2-D dispatch, all engines."""
    c, bsz, q, n = 3, 6, 5, 16
    cfg = neuron.NeuronConfig(n_inputs=n, threshold=7, t_steps=20,
                              dendrite=dendrite, k=2)
    times = _rand_volleys(jax.random.PRNGKey(0), (c, bsz, n), 26)
    w = jax.random.randint(jax.random.PRNGKey(1), (c, q, n), 0, 8)
    per_col = np.stack([
        np.asarray(neuron.fire_times_bank(times[i], w[i], cfg,
                                          backend="closed_form"))
        for i in range(c)])
    for b in BACKENDS:
        got = np.asarray(neuron.fire_times_bank(times, w, cfg, backend=b))
        np.testing.assert_array_equal(per_col, got, err_msg=b)


def test_fire_times_bank_shape_validation():
    cfg = neuron.NeuronConfig(n_inputs=8, threshold=4, t_steps=8)
    with pytest.raises(ValueError):
        neuron.fire_times_bank(jnp.zeros((4, 8), jnp.int32),
                               jnp.zeros((2, 9), jnp.int32), cfg)
    with pytest.raises(ValueError):
        neuron.fire_times_bank(jnp.zeros((2, 4, 8), jnp.int32),
                               jnp.zeros((3, 5, 8), jnp.int32), cfg)


def test_resolve_auto_cpu_without_measurement_is_closed_form():
    if jax.default_backend() == "cpu":
        assert policy.resolve_engine("auto") == "closed_form"
    assert policy.resolve_engine("scan") == "scan"


# ------------------------------------------------------------- rnl clip out
def test_pallas_clip_events_match_scan_diagnostic():
    from repro.kernels import rnl_neuron
    cfg = neuron.NeuronConfig(n_inputs=16, threshold=9, t_steps=24,
                              dendrite="catwalk", k=2)
    times = _rand_volleys(jax.random.PRNGKey(5), (6, 16), 20, p_silent=0.1)
    w = jax.random.randint(jax.random.PRNGKey(6), (4, 16), 1, 8)
    fire, clip = rnl_neuron.rnl_fire_times(
        times, w, t_steps=24, threshold=9, k=2, with_clip=True)
    ref = neuron.simulate_neuron(
        jnp.broadcast_to(times[:, None, :], (6, 4, 16)),
        jnp.broadcast_to(w[None, :, :], (6, 4, 16)), cfg)
    np.testing.assert_array_equal(np.asarray(ref.fire_time),
                                  np.asarray(fire))
    np.testing.assert_array_equal(np.asarray(ref.clip_events),
                                  np.asarray(clip))
    assert int(clip.sum()) > 0  # dense-enough volleys actually clip


def test_pallas_layer_clip_output_shape():
    from repro.kernels import rnl_neuron
    times = _rand_volleys(jax.random.PRNGKey(7), (2, 5, 8), 12)
    w = jax.random.randint(jax.random.PRNGKey(8), (2, 3, 8), 0, 6)
    fire, clip = rnl_neuron.rnl_fire_times_layer(
        times, w, t_steps=16, threshold=5, k=2, with_clip=True)
    assert fire.shape == clip.shape == (2, 5, 3)


# ------------------------------------------------------------------ layer
def _layer_cfg(**kw):
    base = dict(n_columns=1, rf_size=16, n_neurons=3, threshold=12,
                t_steps=16, dendrite="catwalk", k=2,
                stdp=stdp.STDPConfig(mu_capture=1.0, mu_backoff=1.0,
                                     mu_search=0.5),
                backend="closed_form")
    base.update(kw)
    return layer.TNNLayer(**base)


def test_layer_b1_bit_identical_to_column_step_loop():
    """Batched layer forward + minibatch STDP at B=1 == per-volley
    column_step loop (same execution mode), weights and winners."""
    lcfg = _layer_cfg()
    ccfg = lcfg.column_config()
    key = jax.random.PRNGKey(0)
    wl = layer.init_layer(key, lcfg)
    wc = column.init_column(key, ccfg)
    np.testing.assert_array_equal(np.asarray(wl[0]), np.asarray(wc))
    volleys = _rand_volleys(jax.random.PRNGKey(3), (25, 16), 20)
    for i in range(volleys.shape[0]):
        wl, out_l, win_l = layer.layer_step(wl, volleys[i][None, :], lcfg)
        wc, out_c, win_c = column.column_step(wc, volleys[i], ccfg)
        np.testing.assert_array_equal(np.asarray(out_l[0, 0]),
                                      np.asarray(out_c))
        assert int(win_l[0, 0]) == int(win_c)
        np.testing.assert_array_equal(np.asarray(wl[0]), np.asarray(wc))


def test_train_layer_b1_matches_train_column():
    """Scan-compiled training paths agree bit-exactly at C=1, B=1."""
    lcfg = _layer_cfg()
    ccfg = lcfg.column_config()
    key = jax.random.PRNGKey(0)
    volleys = _rand_volleys(jax.random.PRNGKey(9), (40, 16), 20)
    wl, winners_l = layer.train_layer(layer.init_layer(key, lcfg),
                                      volleys, lcfg, batch_size=1)
    wc, winners_c = column.train_column(column.init_column(key, ccfg),
                                        volleys, ccfg)
    np.testing.assert_array_equal(np.asarray(wl[0]), np.asarray(wc))
    np.testing.assert_array_equal(np.asarray(winners_l[:, 0]),
                                  np.asarray(winners_c))


def test_layer_receptive_fields_are_independent_columns():
    """Multi-column forward == per-column column_forward on each RF slice."""
    lcfg = _layer_cfg(n_columns=3, rf_size=8, n_neurons=4, threshold=8)
    ccfg = lcfg.column_config()
    w = layer.init_layer(jax.random.PRNGKey(2), lcfg)
    volleys = _rand_volleys(jax.random.PRNGKey(4), (5, lcfg.n_inputs), 20)
    out, winners = layer.layer_forward(w, volleys, lcfg)
    idx = np.asarray(lcfg.rf_index())
    for b in range(5):
        for c in range(3):
            o_ref, w_ref = column.column_forward(
                w[c], volleys[b][idx[c]], ccfg)
            np.testing.assert_array_equal(np.asarray(out[b, c]),
                                          np.asarray(o_ref))
            assert int(winners[b, c]) == int(w_ref)


def test_layer_overlapping_receptive_fields():
    lcfg = _layer_cfg(n_columns=3, rf_size=8, rf_stride=4, threshold=8)
    assert lcfg.n_inputs == 16
    idx = np.asarray(lcfg.rf_index())
    np.testing.assert_array_equal(idx[:, 0], [0, 4, 8])
    w = layer.init_layer(jax.random.PRNGKey(0), lcfg)
    out, winners = layer.layer_forward(
        w, _rand_volleys(jax.random.PRNGKey(1), (2, 16), 12), lcfg)
    assert out.shape == (2, 3, 3) and winners.shape == (2, 3)


def test_minibatch_stdp_mean_step_invariance():
    """Mean reduction: a minibatch of B identical volleys takes exactly the
    single-volley step (deltas average to the per-volley delta)."""
    lcfg = _layer_cfg()
    w0 = layer.init_layer(jax.random.PRNGKey(1), lcfg)
    v = _rand_volleys(jax.random.PRNGKey(2), (16,), 14)[None, :]
    w1, _, _ = layer.layer_step(w0, v, lcfg)
    w8, _, _ = layer.layer_step(w0, jnp.tile(v, (8, 1)), lcfg)
    np.testing.assert_allclose(np.asarray(w8), np.asarray(w1),
                               rtol=0, atol=1e-6)


def test_train_layer_rejects_ragged_stream():
    lcfg = _layer_cfg()
    volleys = _rand_volleys(jax.random.PRNGKey(0), (10, 16), 12)
    with pytest.raises(ValueError):
        layer.train_layer(layer.init_layer(jax.random.PRNGKey(1), lcfg),
                          volleys, lcfg, batch_size=3)


def test_layer_backends_agree_end_to_end():
    lcfg = _layer_cfg(n_columns=2, rf_size=8, n_neurons=4, threshold=6)
    w = layer.init_layer(jax.random.PRNGKey(3), lcfg)
    volleys = _rand_volleys(jax.random.PRNGKey(4), (9, lcfg.n_inputs), 20)
    ref_out, ref_win = layer.layer_forward(w, volleys, lcfg)
    for b in ("scan", "pallas"):
        out, win = layer.layer_forward(
            w, volleys, dataclasses.replace(lcfg, backend=b))
        np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))
        np.testing.assert_array_equal(np.asarray(ref_win), np.asarray(win))


# ---------------------------------------------------------------- network
def test_network_shape_validation():
    l1 = _layer_cfg(n_columns=2, rf_size=8, n_neurons=4)
    with pytest.raises(ValueError):
        network.make_network([l1, _layer_cfg(rf_size=5)])
    net = network.make_network([l1, _layer_cfg(rf_size=8, threshold=3)])
    assert net.n_inputs == 16 and net.n_outputs == 3


def test_forward_feeds_wta_times_forward():
    l1 = _layer_cfg(n_columns=2, rf_size=8, n_neurons=4, threshold=6)
    l2 = _layer_cfg(n_columns=1, rf_size=8, n_neurons=3, threshold=3)
    net = network.make_network([l1, l2])
    params = network.init_network(jax.random.PRNGKey(0), net)
    volleys = _rand_volleys(jax.random.PRNGKey(1), (6, net.n_inputs), 12)
    res = network.forward(params, volleys, net)
    out, winners = res.out, res.winners
    # layer 2 must see exactly layer 1's flattened WTA output
    out1, _ = layer.layer_forward(params[0], volleys, l1)
    out2, _ = layer.layer_forward(params[1], out1.reshape(6, 8), l2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    assert winners[0].shape == (6, 2) and winners[1].shape == (6, 1)


def test_network_training_smoke():
    l1 = _layer_cfg(n_columns=1, rf_size=16, n_neurons=3)
    l2 = _layer_cfg(n_columns=1, rf_size=3, n_neurons=3, threshold=2)
    net = network.make_network([l1, l2])
    params = network.init_network(jax.random.PRNGKey(0), net)
    volleys = _rand_volleys(jax.random.PRNGKey(1), (24, 16), 14)
    new_params, winners = network.train_network(params, volleys, net,
                                                batch_size=4)
    assert all(np.asarray(p).shape == np.asarray(q).shape
               for p, q in zip(params, new_params))
    for p, lc in zip(new_params, net.layers):
        arr = np.asarray(p)
        assert arr.min() >= 0.0 and arr.max() <= lc.w_max
    assert winners[0].shape == (24, 1)
