"""Gaussian receptive field (GRF) encoding in numpy: the volleys' front end.

A copy of the program's ``core.coding.grf_encode`` (Bohte et al. 2002) kept
with the benchmark, so that the traffic stays the same whatever a later change
does to the program. It follows the program's float32 arithmetic step by step,
including the way ``jax.numpy.linspace`` places the field centers, so the two
agree bit for bit (checked by ``test_chipbench_traffic.py``).
"""

from __future__ import annotations

import numpy as np

#: spike time of a silent line (the program's ``coding.NO_SPIKE``)
NO_SPIKE = 2 ** 30


def _centers(v_min: float, v_max: float, n: int) -> np.ndarray:
    """Field centers as ``jax.numpy.linspace`` computes them in float32."""
    if n == 1:
        return np.asarray([v_min], np.float32)
    start, stop = np.float32(v_min), np.float32(v_max)
    div = n - 1
    step = np.arange(div, dtype=np.float32) / np.float32(div)
    inner = start * (np.float32(1) - step) + stop * step
    return np.concatenate([inner, [stop]]).astype(np.float32)


def value_to_time(values: np.ndarray, t_max: int) -> np.ndarray:
    """Intensities in [0, 1] to spike times: 1 fires at tick 0, 0 never."""
    v = np.clip(values, np.float32(0), np.float32(1))
    t = np.round((np.float32(1) - v) * np.float32(t_max - 1)).astype(np.int32)
    return np.where(v <= 0, np.int32(NO_SPIKE), t).astype(np.int32)


def grf_encode(values: np.ndarray, n_fields: int, t_max: int,
               v_min: float = 0.0, v_max: float = 1.0,
               cutoff: float = 0.05) -> np.ndarray:
    """(..., d) float features to (..., d, n_fields) int32 spike times.

    Each feature is covered by ``n_fields`` Gaussian fields centered evenly
    over ``[v_min, v_max]``, of the program's default width (0.8 x the
    center spacing); a field's activation below ``cutoff`` is silent.
    """
    values = np.asarray(values, np.float32)
    centers = _centers(v_min, v_max, n_fields)
    sigma = 0.8 * (v_max - v_min) / max(n_fields - 1, 1)
    z = (values[..., None] - centers) / np.float32(sigma)
    act = np.exp(np.float32(-0.5) * z ** 2)
    act = np.where(act < np.float32(cutoff), np.float32(0), act)
    return value_to_time(act, t_max)
