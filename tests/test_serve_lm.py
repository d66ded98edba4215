"""LM continuous batching: per-slot KV-cache positions (DESIGN.md §5.2).

Pins the engine's core identity: because attention rows are independent
and decoding is greedy, a request's sampled tokens do not depend on the
batch composition — per-request, static-wave, and continuous (mid-flight
slot re-fill) serving are token-identical; continuous only changes
throughput (fewer steps at mixed request lengths).
"""

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import transformer as T
from repro.serve import Engine, ServeConfig


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("internlm2-1.8b").smoke()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return Engine(params, cfg, ServeConfig(max_len=48))


def _prompts(lengths, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, vocab, (n,)).astype(np.int32) for n in lengths]


def test_continuous_refill_token_identical_and_fewer_steps(lm):
    """Mixed lengths through 2 slots: freed decode slots re-fill
    mid-flight, outputs match the per-request baseline token for token,
    and continuous takes no more steps than the wave baseline."""
    prompts = _prompts((2, 7, 3, 9, 4), lm.cfg.vocab_size)
    per_req = [lm.serve([p], max_new_tokens=6)[0] for p in prompts]
    cont = lm.serve(prompts, max_new_tokens=6, n_slots=2, continuous=True)
    steps_cont = lm.n_steps
    wave = lm.serve(prompts, max_new_tokens=6, n_slots=2, continuous=False)
    steps_wave = lm.n_steps
    for i, (c, w, r) in enumerate(zip(cont, wave, per_req)):
        np.testing.assert_array_equal(c, r, err_msg=f"continuous req {i}")
        np.testing.assert_array_equal(w, r, err_msg=f"wave req {i}")
    assert steps_cont <= steps_wave


def test_generate_routes_attention_families_per_slot(lm):
    """generate() == serve() with one slot per request for KV families."""
    prompts = _prompts((3, 5), lm.cfg.vocab_size, seed=1)
    gen = lm.generate(prompts, max_new_tokens=5)
    ref = [lm.serve([p], max_new_tokens=5)[0] for p in prompts]
    for g, r in zip(gen, ref):
        np.testing.assert_array_equal(g, r)


def test_serve_deterministic_and_bounded(lm):
    prompts = _prompts((4, 2, 6), lm.cfg.vocab_size, seed=2)
    a = lm.serve(prompts, max_new_tokens=4, n_slots=2)
    b = lm.serve(prompts, max_new_tokens=4, n_slots=2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for o in a:
        assert 1 <= len(o) <= 4
        assert (o >= 0).all() and (o < lm.cfg.vocab_size).all()


def test_serve_rejects_empty_prompt_and_bad_slots(lm):
    with pytest.raises(ValueError, match="empty prompt"):
        lm.serve([np.zeros((0,), np.int32)], max_new_tokens=2)
    with pytest.raises(ValueError, match="slot"):
        lm.serve([np.ones((2,), np.int32)], max_new_tokens=2, n_slots=0)


@pytest.mark.parametrize("n_slots", [1, 2, 3, 9])
def test_decode_batch_is_slot_count_and_alone_matches_batched(lm, n_slots):
    """The decode step runs at exactly ``n_slots`` rows (the KV cache holds
    no pad rows), and each request served alone through the same slot
    count matches the batched serve token for token — the form of the
    oracle the chip check uses, since on a TPU a one-row decode matmul
    rounds differently from a wider one (DESIGN.md §5.2)."""
    seen = []
    step = lm._step

    def spy(p, st, t):
        seen.append(t.shape)
        return step(p, st, t)

    prompts = _prompts((2, 5, 3), lm.cfg.vocab_size, seed=3)
    lm._step = spy
    try:
        batched = lm.serve(prompts, max_new_tokens=3, n_slots=n_slots)
    finally:
        lm._step = step
    assert seen and set(seen) == {(n_slots, 1)}
    for i, p in enumerate(prompts):
        alone = lm.serve([p], max_new_tokens=3, n_slots=n_slots)[0]
        np.testing.assert_array_equal(alone, batched[i], err_msg=f"req {i}")


def test_per_slot_state_shapes_and_reset():
    """per_slot_state vectorises cache positions; reset_slots zeroes only
    the freed rows."""
    cfg = get_config("internlm2-1.8b").smoke()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    state = T.per_slot_state(T.init_serve_state(params, cfg, 3, 16), 3)
    assert state.pos.shape == (3,)
    assert state.layer_caches.pos.shape == (cfg.n_layers, 3)
    bumped = state._replace(
        pos=state.pos + 5,
        layer_caches=state.layer_caches._replace(
            pos=state.layer_caches.pos + 5))
    out = T.reset_slots(bumped, np.array([True, False, True]))
    np.testing.assert_array_equal(np.asarray(out.pos), [0, 5, 0])
    np.testing.assert_array_equal(np.asarray(out.layer_caches.pos[0]),
                                  [0, 5, 0])
