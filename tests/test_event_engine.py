"""Event-driven neuron engine: bit-exactness vs scan and closed forms.

The sorted-breakpoint solve (``backend="event"``) must agree with the
cycle-accurate tick scan and the vectorized closed forms on *every* fire
time, across all four dendrite kinds, at any sparsity — including the
degenerate corners: all-silent volleys, zero weights, ramps truncated by
the gamma-cycle end, and potentials that hit the threshold exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import coding, neuron

DENDRITES = ("pc_conventional", "pc_compact", "sorting_pc", "catwalk")
NO_SPIKE = int(coding.NO_SPIKE)


def _sparse_volleys(seed, bsz, n, t_max, p_silent):
    kt, ks = jax.random.split(jax.random.PRNGKey(seed))
    t = jax.random.randint(kt, (bsz, n), 0, t_max)
    silent = jax.random.bernoulli(ks, p_silent, (bsz, n))
    return jnp.where(silent, coding.NO_SPIKE, t)


def _assert_all_engines_agree(times, w, cfg, n_active_max=None):
    ref = np.asarray(neuron.fire_times_bank(times, w, cfg, backend="scan"))
    for backend in ("closed_form", "event"):
        got = np.asarray(neuron.fire_times_bank(times, w, cfg,
                                                backend=backend))
        np.testing.assert_array_equal(ref, got, err_msg=backend)
    if n_active_max is not None:
        got = np.asarray(neuron.fire_times_bank(
            times, w, cfg, backend="event", n_active_max=n_active_max))
        np.testing.assert_array_equal(ref, got, err_msg="event+width")
    return ref


# ------------------------------------------------------------ random sweeps
@pytest.mark.parametrize("dendrite", DENDRITES)
@pytest.mark.parametrize("p_silent", [0.0, 0.5, 0.9])
def test_event_matches_scan_and_closed_form(dendrite, p_silent):
    cfg = neuron.NeuronConfig(n_inputs=16, threshold=9, t_steps=24,
                              dendrite=dendrite, k=2)
    times = _sparse_volleys(17, 7, 16, 30, p_silent)
    w = jax.random.randint(jax.random.PRNGKey(3), (5, 16), 0, 8)
    _assert_all_engines_agree(times, w, cfg, n_active_max=16)


@pytest.mark.parametrize("dendrite", ["pc_compact", "catwalk"])
def test_event_column_stack_3d(dendrite):
    """(C, B, n) dispatch: one compaction serves all columns."""
    cfg = neuron.NeuronConfig(n_inputs=12, threshold=7, t_steps=20,
                              dendrite=dendrite, k=2)
    times = jnp.stack([_sparse_volleys(s, 5, 12, 26, 0.6)
                       for s in (1, 2, 3)])
    w = jax.random.randint(jax.random.PRNGKey(9), (3, 4, 12), 0, 8)
    _assert_all_engines_agree(times, w, cfg)


def test_event_under_jit_uncompacted_fallback():
    """Traced times with no static width: the 2n-event solve still runs
    (and matches) — this is what the serve engine's jit step hits."""
    cfg = neuron.NeuronConfig(n_inputs=16, threshold=8, t_steps=32,
                              dendrite="catwalk", k=2)
    times = _sparse_volleys(5, 6, 16, 40, 0.7)
    w = jax.random.randint(jax.random.PRNGKey(4), (3, 16), 0, 8)
    fn = jax.jit(lambda t: neuron.fire_times_bank(t, w, cfg,
                                                  backend="event"))
    want = neuron.fire_times_bank(times, w, cfg, backend="scan")
    np.testing.assert_array_equal(np.asarray(want), np.asarray(fn(times)))


def test_event_under_jit_with_static_width():
    """Compacted solve inside jit when the width is pinned statically."""
    cfg = neuron.NeuronConfig(n_inputs=16, threshold=8, t_steps=32,
                              dendrite="catwalk", k=2)
    times = _sparse_volleys(6, 6, 16, 40, 0.8)
    w = jax.random.randint(jax.random.PRNGKey(4), (3, 16), 0, 8)
    fn = jax.jit(lambda t: neuron.fire_times_bank(
        t, w, cfg, backend="event", n_active_max=8))
    want = neuron.fire_times_bank(times, w, cfg, backend="scan")
    np.testing.assert_array_equal(np.asarray(want), np.asarray(fn(times)))


# --------------------------------------------------------------- edge cases
def test_event_all_silent_volley():
    cfg = neuron.NeuronConfig(n_inputs=8, threshold=1, t_steps=16,
                              dendrite="pc_compact")
    times = jnp.full((3, 8), coding.NO_SPIKE, jnp.int32)
    w = jnp.full((2, 8), 7, jnp.int32)
    got = _assert_all_engines_agree(times, w, cfg)
    assert (got == NO_SPIKE).all()


def test_event_zero_weights_never_fire():
    """w=0 lines raise no ramp bits: their on/off breakpoints cancel."""
    cfg = neuron.NeuronConfig(n_inputs=8, threshold=1, t_steps=16,
                              dendrite="pc_compact")
    times = jnp.zeros((2, 8), jnp.int32)      # every line spikes at t=0
    w = jnp.zeros((2, 8), jnp.int32)          # ...with zero weight
    got = _assert_all_engines_agree(times, w, cfg)
    assert (got == NO_SPIKE).all()


def test_event_negative_weights_are_inert():
    """w<0 lines have an empty ramp window [0, w) in the scan; the event
    engine must floor them to zero-length segments, not let the early
    off-breakpoint depress the count under other lines' ramps
    (regression: [[0, 8]] x [[10, -5]] fired NO_SPIKE instead of 5)."""
    cfg = neuron.NeuronConfig(n_inputs=2, threshold=6, t_steps=16,
                              dendrite="pc_compact")
    times = jnp.array([[0, 8]], jnp.int32)
    w = jnp.array([[10, -5]], jnp.int32)
    got = _assert_all_engines_agree(times, w, cfg)
    assert (got == 5).all()


def test_event_ramp_truncated_by_cycle_end():
    """Spikes near T with long ramps: the off-breakpoint lands past the
    cycle and must clamp, not fire late."""
    cfg = neuron.NeuronConfig(n_inputs=4, threshold=6, t_steps=12,
                              dendrite="pc_compact")
    times = jnp.array([[9, 10, 11, coding.NO_SPIKE],
                       [11, 11, 11, 11]], jnp.int32)
    w = jnp.array([[7, 7, 7, 7]], jnp.int32)
    _assert_all_engines_agree(times, w, cfg)


def test_event_spike_at_or_past_cycle_end_is_inert():
    """times >= t_steps (but < NO_SPIKE) contribute nothing."""
    cfg = neuron.NeuronConfig(n_inputs=4, threshold=2, t_steps=8,
                              dendrite="pc_compact")
    times = jnp.array([[8, 9, 100, coding.NO_SPIKE]], jnp.int32)
    w = jnp.array([[7, 7, 7, 7]], jnp.int32)
    got = _assert_all_engines_agree(times, w, cfg)
    assert (got == NO_SPIKE).all()


def test_event_exact_threshold_tie():
    """Potential reaching the threshold exactly at a breakpoint tick: the
    crossing must land on that tick, not one off. One line, w=3 ramp from
    t=2 -> potential 1,2,3 at ticks 2,3,4; threshold=3 fires at t=4."""
    cfg = neuron.NeuronConfig(n_inputs=2, threshold=3, t_steps=16,
                              dendrite="pc_compact")
    times = jnp.array([[2, coding.NO_SPIKE]], jnp.int32)
    w = jnp.array([[3, 5]], jnp.int32)
    got = _assert_all_engines_agree(times, w, cfg)
    assert int(got[0, 0]) == 4


def test_event_threshold_met_on_first_tick():
    cfg = neuron.NeuronConfig(n_inputs=4, threshold=4, t_steps=8,
                              dendrite="pc_compact")
    times = jnp.zeros((1, 4), jnp.int32)
    w = jnp.full((1, 4), 2, jnp.int32)
    got = _assert_all_engines_agree(times, w, cfg)
    assert int(got[0, 0]) == 0


def test_event_nonpositive_threshold_matches_scan():
    """threshold <= 0: the scan fires at tick 0 unconditionally."""
    cfg = neuron.NeuronConfig(n_inputs=4, threshold=0, t_steps=8,
                              dendrite="pc_compact")
    times = jnp.full((2, 4), coding.NO_SPIKE, jnp.int32)
    w = jnp.full((1, 4), 3, jnp.int32)
    _assert_all_engines_agree(times, w, cfg)


def test_event_catwalk_clip_changes_fire_time():
    """Dense burst with k=2: the clipped dendrite integrates slower, so
    the event engine must reproduce the *clipped* trajectory exactly."""
    cfg_pc = neuron.NeuronConfig(n_inputs=6, threshold=8, t_steps=32,
                                 dendrite="pc_compact")
    cfg_cw = neuron.NeuronConfig(n_inputs=6, threshold=8, t_steps=32,
                                 dendrite="catwalk", k=2)
    times = jnp.zeros((1, 6), jnp.int32)          # 6 simultaneous ramps
    w = jnp.full((1, 6), 7, jnp.int32)
    pc = _assert_all_engines_agree(times, w, cfg_pc)
    cw = _assert_all_engines_agree(times, w, cfg_cw)
    assert int(pc[0, 0]) < int(cw[0, 0])          # clip delays the spike


# ------------------------------------------------------ property-based sweep
@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       bsz=st.integers(1, 6), q=st.integers(1, 5), n=st.integers(1, 20),
       t_steps=st.integers(1, 40), threshold=st.integers(1, 16),
       k=st.integers(1, 4), p_silent=st.sampled_from([0.0, 0.5, 0.9, 1.0]),
       dendrite=st.sampled_from(["pc_compact", "catwalk"]))
def test_event_property_random_sparse_volleys(seed, bsz, q, n, t_steps,
                                              threshold, k, p_silent,
                                              dendrite):
    """event == scan == closed_form over random sparse volleys, including
    spikes past the cycle end and weights that truncate at t_steps."""
    cfg = neuron.NeuronConfig(n_inputs=n, threshold=threshold,
                              t_steps=t_steps, dendrite=dendrite, k=k)
    kt, ks, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    t = jax.random.randint(kt, (bsz, n), 0, t_steps + 8)
    silent = jax.random.bernoulli(ks, p_silent, (bsz, n))
    times = jnp.where(silent, coding.NO_SPIKE, t)
    w = jax.random.randint(kw, (q, n), 0, 8)
    _assert_all_engines_agree(times, w, cfg)
