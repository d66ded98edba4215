"""Plain TNN reference in jax.numpy, independent of the program under test.

The semantics, from the Catwalk paper (arXiv 2508.21267) and the TNN papers it
builds on, written straight from a configuration file:

  * a layer of C columns; column c reads input lines
    ``[c * stride, c * stride + rf_size)``, and a recurrent layer also its own
    Q output lines of the previous gamma cycle;
  * synapse weights are float registers read as integers (round half to
    even); an input spiking at tick ``x`` with weight ``w`` raises one bit
    per tick for ``x <= t < x + w`` (ramp-no-leak);
  * the Catwalk dendrite adds ``min(popcount, k)`` bits per tick to the
    membrane potential; a neuron fires at the first tick its potential
    reaches the threshold;
  * 1-WTA per column: the earliest neuron keeps its spike (ties to the
    lowest index), the others are silenced;
  * STDP, layer-local after each cycle at the cycle's starting weights:
    capture / backoff / search / ghost cases scaled by the stabilizer
    ``max(4u(1-u), floor)``, ``u = w / w_max``, only for the column's winner
    (every neuron when the column is silent), averaged over the batch rows
    and clipped to ``[0, w_max]``.

It imports nothing of the program. ``wdtype`` stores the weights in another
float type: ``bfloat16`` is the control, the nearest precision below the
configuration's float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import work

NO_SPIKE = 2 ** 30


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    n_columns: int
    rf_size: int
    stride: int
    n_neurons: int
    threshold: int
    recurrent: bool

    @property
    def rf_total(self) -> int:
        return self.rf_size + (self.n_neurons if self.recurrent else 0)

    @property
    def n_outputs(self) -> int:
        return self.n_columns * self.n_neurons


@dataclasses.dataclass(frozen=True)
class NetSpec:
    layers: Tuple[LayerSpec, ...]
    t_steps: int
    k: Optional[int]
    w_max: int
    learn: bool
    mu_capture: float
    mu_backoff: float
    mu_search: float
    stabilize: bool
    stab_floor: float
    reduction: str


def net_spec(config: Dict) -> NetSpec:
    """The reference's reading of a configuration file."""
    layers = tuple(LayerSpec(
        n_columns=int(lay["n_columns"]), rf_size=int(lay["rf_size"]),
        stride=int(lay.get("rf_stride") or lay["rf_size"]),
        n_neurons=int(lay["n_neurons"]), threshold=int(lay["threshold"]),
        recurrent=bool(lay.get("recurrent", False)))
        for lay in config["layers"])
    st = config.get("stdp", {})
    clipped = config["dendrite"] in ("catwalk", "sorting_pc")
    return NetSpec(
        layers=layers, t_steps=int(config["t_steps"]),
        k=int(config["k"]) if clipped else None, w_max=int(config["w_max"]),
        learn=bool(config.get("learn", False)),
        mu_capture=float(st.get("mu_capture", 1.0)),
        mu_backoff=float(st.get("mu_backoff", 0.5)),
        mu_search=float(st.get("mu_search", 0.25)),
        stabilize=bool(st.get("stabilize", True)),
        stab_floor=float(st.get("stab_floor", 0.25)),
        reduction=str(st.get("reduction", "mean")))


def init_weights(key: jax.Array, config: Dict) -> Tuple[jax.Array, ...]:
    """Per-layer (C, Q, R) float32 weights uniform over [0, w_max]; one
    jitted call on the device."""
    shapes = tuple((int(lay["n_columns"]), int(lay["n_neurons"]),
                    work.rf_total(lay)) for lay in config["layers"])
    w_max = float(config["w_max"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return tuple(jax.random.uniform(k, s, jnp.float32, 0.0, w_max)
                     for k, s in zip(keys, shapes))

    return make(key)


def _gather(x, lay: LayerSpec, carry):
    """(B, n_in) volley -> (B, C, R) per-column receptive fields."""
    idx = (np.arange(lay.n_columns)[:, None] * lay.stride
           + np.arange(lay.rf_size)[None, :])
    xs = x[:, idx]
    if lay.recurrent:
        b = x.shape[0]
        xs = jnp.concatenate(
            [xs, carry.reshape(b, lay.n_columns, lay.n_neurons)], axis=-1)
    return xs


def _fire(xs, w_int, lay: LayerSpec, spec: NetSpec):
    """(B, C, R) spike times x (C, Q, R) int weights -> (B, C, Q) fire times."""
    x = xs[:, :, None, :]
    end = x + w_int[None]
    shape = (xs.shape[0], lay.n_columns, lay.n_neurons)

    def tick(t, carry):
        pot, fire = carry
        bits = jnp.sum(((x <= t) & (t < end)).astype(jnp.int32), axis=-1)
        pot = pot + (bits if spec.k is None else jnp.minimum(bits, spec.k))
        fire = jnp.where((pot >= lay.threshold) & (fire == NO_SPIKE), t, fire)
        return pot, fire

    init = (jnp.zeros(shape, jnp.int32), jnp.full(shape, NO_SPIKE, jnp.int32))
    return jax.lax.fori_loop(0, spec.t_steps, tick, init)[1]


def _wta(fire, lay: LayerSpec):
    """1-WTA: (B, C, Q) fire times -> (post-WTA times, winners (B, C))."""
    any_fire = jnp.any(fire < NO_SPIKE, axis=-1)
    win = jnp.where(any_fire, jnp.argmin(fire, axis=-1), -1).astype(jnp.int32)
    lane = jnp.arange(lay.n_neurons, dtype=jnp.int32)
    return jnp.where(lane == win[..., None], fire, NO_SPIKE), win


def _stdp(w, xs, out, win, spec: NetSpec):
    """Minibatch STDP of one layer; laid out (C, B, Q, R), reduced over B."""
    x_t = jnp.swapaxes(xs, 0, 1)[:, :, None, :]        # (C, B, 1, R)
    y_t = jnp.swapaxes(out, 0, 1)[..., None]           # (C, B, Q, 1)
    x, y = x_t < NO_SPIKE, y_t < NO_SPIKE
    causal = x & y & (x_t <= y_t)
    anti = x & y & (x_t > y_t)
    search = x & ~y
    ghost = ~x & y
    if spec.stabilize:
        u = w / spec.w_max
        b = jnp.maximum(4.0 * u * (1.0 - u), spec.stab_floor)
    else:
        b = jnp.ones_like(w)
    b = b[:, None]                                      # (C, 1, Q, R)
    d = (causal * spec.mu_capture * b - anti * spec.mu_backoff * b
         + search * spec.mu_search - ghost * spec.mu_backoff * b)
    q = jnp.arange(out.shape[-1], dtype=jnp.int32)
    win_t = jnp.swapaxes(win, 0, 1)[..., None]          # (C, B, 1)
    keep = (q == win_t) | (win_t < 0)                   # (C, B, Q)
    acc = jnp.sum(jnp.where(keep[..., None], d, 0.0), axis=1)
    if spec.reduction == "mean":
        acc = acc / out.shape[0]
    return jnp.clip(w + acc, 0.0, float(spec.w_max))


def step(params, x, carry, spec: NetSpec, wdtype=jnp.float32):
    """One gamma cycle of the whole stack for a batch of volleys.

    ``params`` per-layer float weights (already in ``wdtype``'s values);
    ``carry`` per-layer previous outputs (B, n_outputs), None for
    feedforward layers. Returns (last layer's (B, C, Q) output, next carry,
    next params); the params advance only when the configuration learns.
    """
    outs, new_params, carry_out = None, [], []
    for w, lay, c in zip(params, spec.layers, carry):
        w_int = jnp.round(w).astype(jnp.int32)
        xs = _gather(x, lay, c)
        out, win = _wta(_fire(xs, w_int, lay, spec), lay)
        if spec.learn:
            new_params.append(
                _stdp(w, xs, out, win, spec).astype(wdtype).astype(jnp.float32))
        else:
            new_params.append(w)
        x = out.reshape(out.shape[0], lay.n_outputs)
        carry_out.append(x if lay.recurrent else None)
        outs = out
    return outs, tuple(carry_out), tuple(new_params)


def in_dtype(params, wdtype) -> Tuple[jax.Array, ...]:
    """Float32 weights with the values ``wdtype`` can hold."""
    return tuple(jnp.asarray(p).astype(wdtype).astype(jnp.float32)
                 for p in params)


def pool_outputs(params, pool: np.ndarray, spec: NetSpec, wdtype=jnp.float32,
                 block: int = 256) -> np.ndarray:
    """Last-layer outputs (P, C, Q) of every pool volley through a
    feedforward network with fixed weights, in blocks of rows."""
    if spec.learn or any(lay.recurrent for lay in spec.layers):
        raise ValueError("pool_outputs needs a feedforward, non-learning net")
    params = in_dtype(params, wdtype)
    none = (None,) * len(spec.layers)
    fwd = jax.jit(lambda p, x: step(p, x, none, spec)[0])
    outs = []
    for lo in range(0, pool.shape[0], block):
        x = pool[lo:lo + block]
        pad = block - x.shape[0]
        if pad:
            x = np.concatenate([x, np.full((pad, x.shape[1]), NO_SPIKE,
                                           np.int32)])
        outs.append(np.asarray(fwd(params, jnp.asarray(x)))[:block - pad])
    return np.concatenate(outs)


@functools.partial(jax.jit, static_argnames=("spec", "wdtype"))
def _replay_chunk(params, carry, pool, rows, fresh, spec, wdtype):
    def one(state, xs):
        p, c = state
        r, f = xs
        live = r >= 0
        x = jnp.where(live[:, None], pool[jnp.maximum(r, 0)], NO_SPIKE)
        reset = (f | ~live)[:, None]
        c = tuple(None if ci is None else jnp.where(reset, NO_SPIKE, ci)
                  for ci in c)
        out, c_next, p_next = step(p, x, c, spec, wdtype)
        return (p_next, c_next), out

    (params, carry), outs = jax.lax.scan(one, (params, carry), (rows, fresh))
    return params, carry, outs


def replay(params, pool: np.ndarray, rows: np.ndarray, fresh: np.ndarray,
           spec: NetSpec, wdtype=jnp.float32, chunk: int = 128):
    """Serve a recorded schedule through the reference, step by step.

    ``rows[s, i]`` is the pool index of the volley in batch row i at step s
    (-1 for an empty row); ``fresh[s, i]`` marks a stream's first volley (its
    recurrent carry starts silent). Returns (outputs (S, B, C, Q), final
    float32 weights).
    """
    params = in_dtype(params, wdtype)
    b = rows.shape[1]
    carry = tuple(jnp.full((b, lay.n_outputs), NO_SPIKE, jnp.int32)
                  if lay.recurrent else None for lay in spec.layers)
    pool_dev = jnp.asarray(pool)
    n = rows.shape[0]
    outs = []
    for lo in range(0, n, chunk):
        r, f = rows[lo:lo + chunk], fresh[lo:lo + chunk]
        pad = chunk - r.shape[0]
        if pad:   # empty steps: no input spike, so no output and no update
            r = np.concatenate([r, np.full((pad, b), -1, np.int32)])
            f = np.concatenate([f, np.zeros((pad, b), bool)])
        params, carry, out = _replay_chunk(params, carry, pool_dev,
                                           jnp.asarray(r), jnp.asarray(f),
                                           spec, wdtype)
        outs.append(np.asarray(out)[:chunk - pad])
    return np.concatenate(outs), tuple(np.asarray(p) for p in params)
