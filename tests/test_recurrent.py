"""Recurrent TNN layers and carry-threaded ``network.forward`` (§6.5).

A recurrent layer's columns see their own previous-cycle post-WTA output
volley appended after the feedforward receptive-field window (Q extra
weight columns per neuron). The contract pinned here:

* bit-exactness vs a manually unrolled per-layer reference across the
  scan / closed_form / event engines;
* an all-silent carry (``init_carry``) makes cycle 0 exactly the
  feedforward network — recurrence adds nothing until something fires;
* carry threading composes with the pipelined schedule
  (``microbatches=M``) without changing a spike time;
* the engine round-trips a stream's carry through the slot pool
  (``final_state`` -> ``initial_state`` continuation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coding, layer, network
from repro.serve import TNNEngine, TNNServeConfig, tnn_engine

NO_SPIKE = int(coding.NO_SPIKE)

JNP_BACKENDS = ("scan", "closed_form", "event")


def _rec_net(backend="scan", t_steps=12):
    l1 = layer.TNNLayer(n_columns=4, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=t_steps, dendrite="catwalk", k=2,
                        backend=backend, recurrent=True)
    l2 = layer.TNNLayer(n_columns=3, rf_size=4, n_neurons=2, threshold=4,
                        t_steps=t_steps, dendrite="catwalk", k=2,
                        backend=backend, recurrent=True)
    return network.make_network([l1, l2])


def _volley_seq(seed, cycles, bsz, n, t_steps=12):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, 2 * t_steps, size=(cycles, bsz, n))
    return np.where(t >= t_steps, NO_SPIKE, t).astype(np.int32)


def _unrolled_reference(params, net, seq):
    """Manual per-layer unroll: layer_forward with explicit carries."""
    carries = [layer.carry_init(lc, seq.shape[1]) if lc.recurrent else None
               for lc in net.layers]
    outs = []
    for v in seq:
        x = jnp.asarray(v)
        out = None
        for i, lc in enumerate(net.layers):
            out, _ = layer.layer_forward(params[i], x, lc,
                                         carry=carries[i])
            x = out.reshape(x.shape[0], -1)
            if lc.recurrent:
                carries[i] = x
        outs.append(np.asarray(out))   # last layer's (B, C, Q) volley
    return outs, carries


# --------------------------------------------------------- layer level
def test_recurrent_layer_shapes_and_weight_plane():
    lc = _rec_net().layers[0]
    assert lc.rf_total == lc.rf_size + lc.n_neurons
    w = layer.init_layer(jax.random.PRNGKey(0), lc)
    assert w.shape == (lc.n_columns, lc.n_neurons, lc.rf_total)
    c = layer.carry_init(lc, 5)
    assert c.shape == (5, lc.n_outputs)
    assert (np.asarray(c) == NO_SPIKE).all()


def test_carry_for_feedforward_layer_raises():
    lc = layer.TNNLayer(n_columns=2, rf_size=4, n_neurons=2, threshold=3,
                        t_steps=8, dendrite="catwalk", k=1)
    w = layer.init_layer(jax.random.PRNGKey(0), lc)
    v = jnp.zeros((3, lc.n_inputs), jnp.int32)
    with pytest.raises(ValueError, match="non-recurrent"):
        layer.layer_forward(w, v, lc, carry=jnp.zeros((3, 4), jnp.int32))


@pytest.mark.parametrize("backend", JNP_BACKENDS)
def test_silent_carry_equals_feedforward_cycle(backend):
    """init_carry (all NO_SPIKE) contributes nothing: cycle 0 of a
    recurrent net == the same-weights feedforward pass over rf lines."""
    net = _rec_net(backend)
    params = network.init_network(jax.random.PRNGKey(0), net)
    v = jnp.asarray(_volley_seq(3, 1, 6, net.n_inputs)[0])
    res = network.forward(params, v, net,
                          carry=network.init_carry(net, 6))
    res_default = network.forward(params, v, net)       # carry=None
    np.testing.assert_array_equal(np.asarray(res.out),
                                  np.asarray(res_default.out))


@pytest.mark.parametrize("backend", JNP_BACKENDS)
def test_recurrent_forward_matches_unrolled_reference(backend):
    """Multi-cycle carry threading == the manual per-layer unroll."""
    net = _rec_net(backend)
    params = network.init_network(jax.random.PRNGKey(1), net)
    seq = _volley_seq(7, 4, 5, net.n_inputs)
    ref_outs, ref_carries = _unrolled_reference(params, net, seq)
    carry = None
    for v, ref in zip(seq, ref_outs):
        res = network.forward(params, jnp.asarray(v), net, carry=carry)
        np.testing.assert_array_equal(np.asarray(res.out), ref)
        carry = res.carry
    for got, want in zip(carry, ref_carries):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_backends_bit_exact_with_carry():
    nets = {b: _rec_net(b) for b in JNP_BACKENDS}
    params = network.init_network(jax.random.PRNGKey(2), nets["scan"])
    seq = _volley_seq(11, 3, 4, nets["scan"].n_inputs)
    outs = {}
    for b, net in nets.items():
        carry, got = None, []
        for v in seq:
            res = network.forward(params, jnp.asarray(v), net, carry=carry)
            got.append(np.asarray(res.out))
            carry = res.carry
        outs[b] = got
    for b in ("closed_form", "event"):
        for got, want in zip(outs[b], outs["scan"]):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("microbatches", [2, 3, 5])
def test_recurrent_composes_with_pipelined_schedule(microbatches):
    """carry= and microbatches= together: same spikes, same carry."""
    net = _rec_net()
    params = network.init_network(jax.random.PRNGKey(3), net)
    seq = _volley_seq(13, 3, 6, net.n_inputs)
    carry_b = carry_p = None
    for v in seq:
        rb = network.forward(params, jnp.asarray(v), net, carry=carry_b)
        rp = network.forward(params, jnp.asarray(v), net, carry=carry_p,
                             microbatches=microbatches)
        np.testing.assert_array_equal(np.asarray(rb.out),
                                      np.asarray(rp.out))
        for a, b in zip(rb.carry, rp.carry):
            if a is None:
                assert b is None
            else:
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
        carry_b, carry_p = rb.carry, rp.carry


def test_mixed_recurrent_feedforward_stack():
    """Only the recurrent layer carries state; the feedforward layer's
    carry slot stays None through threading."""
    l1 = layer.TNNLayer(n_columns=4, rf_size=4, n_neurons=3, threshold=5,
                        t_steps=12, dendrite="catwalk", k=2, recurrent=True)
    l2 = layer.TNNLayer(n_columns=3, rf_size=4, n_neurons=2, threshold=4,
                        t_steps=12, dendrite="catwalk", k=2)
    net = network.make_network([l1, l2])
    params = network.init_network(jax.random.PRNGKey(4), net)
    seq = _volley_seq(17, 3, 4, net.n_inputs)
    ref_outs, _ = _unrolled_reference(params, net, seq)
    carry = None
    for v, ref in zip(seq, ref_outs):
        res = network.forward(params, jnp.asarray(v), net, carry=carry)
        np.testing.assert_array_equal(np.asarray(res.out), ref)
        carry = res.carry
        assert carry[1] is None


def test_single_volley_carry_promotion():
    """1-D volley + 1-D carry promote and squeeze symmetrically."""
    net = _rec_net()
    params = network.init_network(jax.random.PRNGKey(5), net)
    seq = _volley_seq(19, 2, 1, net.n_inputs)
    r0 = network.forward(params, jnp.asarray(seq[0][0]), net)
    last = net.layers[-1]
    assert r0.out.shape == (last.n_columns, last.n_neurons)  # batch squeezed
    assert all(c is None or c.ndim == 1 for c in r0.carry)
    r1 = network.forward(params, jnp.asarray(seq[1][0]), net,
                         carry=r0.carry)                     # 1-D carry
    carry_2d = tuple(c[None] if c is not None else None for c in r0.carry)
    rb = network.forward(params, jnp.asarray(seq[1]), net, carry=carry_2d)
    np.testing.assert_array_equal(np.asarray(r1.out),
                                  np.asarray(rb.out[0]))


def test_forward_validates_carry_length():
    net = _rec_net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    v = jnp.zeros((2, net.n_inputs), jnp.int32)
    with pytest.raises(ValueError, match="carry"):
        network.forward(params, v, net,
                        carry=(jnp.zeros((2, 12), jnp.int32),))


# --------------------------------------------------------- serving path
@pytest.mark.parametrize("backend", ("auto", "scan", "event"))
def test_engine_recurrent_streams_bit_exact(backend):
    """Recurrent streams through the slot pool (mid-flight re-fill churn)
    == per-stream reference with explicitly threaded carry."""
    net = _rec_net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    rng = np.random.default_rng(0)
    streams = [_volley_seq(int(rng.integers(1e9)),
                           int(rng.integers(1, 5)), 1,
                           net.n_inputs)[:, 0] for _ in range(7)]
    eng = TNNEngine(params, net,
                    TNNServeConfig(n_slots=3, backend=backend))
    assert eng.stateful
    results = eng.serve([s.copy() for s in streams])
    for s, r in zip(streams, results):
        np.testing.assert_array_equal(
            tnn_engine.reference_outputs(params, net, s), r)


def test_engine_stream_continuation_via_final_state():
    """retire hands back the stream's final carry; resubmitting it as
    initial_state continues the stream exactly (split == unsplit)."""
    net = _rec_net()
    params = network.init_network(jax.random.PRNGKey(0), net)
    seq = _volley_seq(29, 6, 1, net.n_inputs)[:, 0]
    eng = TNNEngine(params, net, TNNServeConfig(n_slots=2))
    full = eng.serve([seq])[0]
    req_a = eng.submit(seq[:3])
    eng.run()
    req_b = eng.submit(seq[3:], initial_state=req_a.final_state)
    eng.run()
    np.testing.assert_array_equal(
        np.concatenate([req_a.result(), req_b.result()]), full)
