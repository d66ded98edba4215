"""The one general traffic generator: every mix is a file of parameters.

The volleys are frames of a grey-level image sensor, as the configuration's
input states it (``image_side`` pixels square). Each pixel is encoded by the
GRF front end (``grf.py``) with ``n_fields`` fields over ``t_input`` ticks;
two fields make the on/off (positive/negative) pair of lines a TNN reads per
pixel. Layer 1's columns read ``patch_side`` x ``patch_side`` patches at
stride 1, and the program's layers read 1-D windows of lines, so each volley
is sent unfolded: column by column, each column's patch in row-major order,
each pixel's fields together (``unfold_index``).

The frames come from scenes: a few strokes, anti-aliased, that drift by a
fraction of a pixel per frame, so consecutive frames of a scene are alike
and a request (a run of consecutive frames of one scene) is one sensor's
short sequence. ``POOL_VOLLEYS`` frames are made per run, ``SCENE_FRAMES``
to a scene.

A mix (``traffic/<name>.json``) gives:

  loop            "closed": ``clients`` streams, each sending its next
                  request the moment the previous one retires (zero think
                  time); "open": Poisson arrivals at ``rate_per_s``, sent on
                  schedule whatever the system does.
  length_min/max  frames (volleys, one per gamma cycle) per request,
                  uniform over the range.

Other keys (``basis``) document the mix and are not read. Everything is
drawn from ``--seed``. Every seed gets the same multiset of request lengths
and of inter-arrival gaps, in another order: the seed changes which frames
are sent and in what order, not how much work there is.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from harness import grf

#: frames made per run; a request never holds more than one scene's frames
POOL_VOLLEYS = 2048
#: consecutive frames of one scene in the pool
SCENE_FRAMES = 16
#: strokes per scene, and their half-width in pixels
STROKES = (3, 5)
HALF_WIDTH = (1.0, 1.8)
#: largest drift per frame, in pixels along each axis
DRIFT = 0.5
#: GRF activation below which a line stays silent: with two fields it only
#: silences the grey edges of strokes, so it cannot thin the volleys
GRF_CUTOFF = 0.5
#: requests drawn ahead for a closed loop (reused cyclically past this)
CLOSED_DRAWS = 1 << 16


def seed_rng(seed: int, tag: int) -> np.random.Generator:
    """Numpy generator for one use (``tag``) of the run seed; any integer."""
    return np.random.default_rng([seed % (1 << 64), tag])


def seed_key_int(seed: int, tag: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey``, derived from the seed."""
    ss = np.random.SeedSequence([seed % (1 << 64), tag])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def unfold_index(config: Dict) -> np.ndarray:
    """(C * patch^2 * fields,) line of the encoded image behind each input
    line of layer 1: column (i, j) reads the patch at row i, column j."""
    side, patch = int(config["image_side"]), int(config["patch_side"])
    fields = int(config["n_fields"])
    n = side - patch + 1
    i, j, di, dj, f = np.meshgrid(np.arange(n), np.arange(n),
                                  np.arange(patch), np.arange(patch),
                                  np.arange(fields), indexing="ij")
    return (((i + di) * side + (j + dj)) * fields + f).reshape(-1)


def frames(config: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """(n, side, side) float32 intensities in [0, 1], ``SCENE_FRAMES`` to a
    scene: strokes (segments) that drift together."""
    side = int(config["image_side"])
    n_scenes = -(-n // SCENE_FRAMES)
    yx = np.stack(np.meshgrid(np.arange(side), np.arange(side),
                              indexing="ij"), -1).astype(np.float32)
    out = np.zeros((n_scenes * SCENE_FRAMES, side, side), np.float32)
    t = np.arange(SCENE_FRAMES, dtype=np.float32)[:, None]
    lo, hi = 0.15 * side, 0.85 * side
    for s in range(n_scenes):
        drift = rng.uniform(-DRIFT, DRIFT, 2).astype(np.float32) * t
        img = out[s * SCENE_FRAMES:(s + 1) * SCENE_FRAMES]
        for _ in range(int(rng.integers(STROKES[0], STROKES[1] + 1))):
            a, b = rng.uniform(lo, hi, (2, 2)).astype(np.float32)
            hw = np.float32(rng.uniform(*HALF_WIDTH))
            ab = b - a
            # distance of every pixel to the segment, per frame
            p = yx[None] - (a + drift)[:, None, None]
            u = np.clip((p @ ab) / max(float(ab @ ab), 1e-6), 0, 1)
            d = np.linalg.norm(p - u[..., None] * ab, axis=-1)
            np.maximum(img, np.clip(hw + 0.5 - d, 0, 1), out=img)
    return out[:n]


def volley_pool(config: Dict, seed: int) -> np.ndarray:
    """(POOL_VOLLEYS, n_inputs) int32 volleys: the seed's frames, encoded and
    unfolded for layer 1."""
    img = frames(config, POOL_VOLLEYS, seed_rng(seed, 1))
    idx = unfold_index(config)
    out = np.empty((POOL_VOLLEYS, idx.size), np.int32)
    for lo in range(0, POOL_VOLLEYS, 256):          # bounded temporaries
        enc = grf.grf_encode(img[lo:lo + 256], int(config["n_fields"]),
                             int(config["t_input"]),
                             cutoff=GRF_CUTOFF)
        out[lo:lo + 256] = enc.reshape(enc.shape[0], -1)[:, idx]
    return out


def _balanced_lengths(traffic: Dict, n: int) -> np.ndarray:
    lo, hi = int(traffic["length_min"]), int(traffic["length_max"])
    return lo + np.arange(n) % (hi - lo + 1)


def _offsets(lengths: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """A start frame per request inside one scene of the pool."""
    scene = rng.integers(0, POOL_VOLLEYS // SCENE_FRAMES, lengths.size)
    first = rng.integers(0, SCENE_FRAMES - lengths + 1)
    return scene * SCENE_FRAMES + first


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Requests in the order they are sent.

    ``due`` is each request's send time in seconds from the window's start
    (open loop), or None for a closed loop, where a request is due when its
    client's previous one retires. ``clients`` is the closed loop's number of
    concurrent streams (0 for an open loop).
    """

    lengths: np.ndarray
    offsets: np.ndarray
    due: np.ndarray | None
    clients: int


def schedule(traffic: Dict, seconds: float, seed: int) -> Schedule:
    """The mix's requests for a window of ``seconds``, drawn from ``seed``."""
    if int(traffic["length_max"]) > SCENE_FRAMES:
        raise ValueError(f"requests longer than a scene: {traffic}")
    rng = seed_rng(seed, 2)
    if traffic["loop"] == "closed":
        lengths = rng.permutation(_balanced_lengths(traffic, CLOSED_DRAWS))
        return Schedule(lengths=lengths, offsets=_offsets(lengths, rng),
                        due=None, clients=int(traffic["clients"]))
    if traffic["loop"] != "open":
        raise ValueError(f"unknown traffic kind: {traffic}")
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    # exponential quantiles at the midpoints: the same gaps for every seed,
    # mean just under 1/rate, so every arrival falls inside the window
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = np.cumsum(gaps)
    due *= min(1.0, seconds / due[-1] * (1 - 1e-9))
    lengths = rng.permutation(_balanced_lengths(traffic, n))
    return Schedule(lengths=lengths, offsets=_offsets(lengths, rng), due=due,
                    clients=0)


def stream(pool: np.ndarray, offset: int, length: int) -> np.ndarray:
    """One request's volleys: ``length`` consecutive pool volleys."""
    return pool[(offset + np.arange(length)) % pool.shape[0]]
