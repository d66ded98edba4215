"""Operations and bytes the RNL algorithm needs, from a configuration's shapes.

Counted from the logical shapes, so the work is the same whatever implements
it: batch rows are the configuration's ``n_slots`` (no tile or lane padding),
and spike times and weights count at int32, the width the API takes.

Per call of a layer's neuron bank (C columns, B rows, Q neurons, R dendritic
inputs, T ticks):

  ops   = 2 * C * B * Q * R * T     one ramp compare and one accumulate per
                                    synapse per tick
  bytes = 4 * (C*B*R + C*Q*R + C*B*Q)   spike times and weights read once,
                                        fire times written once
"""

from __future__ import annotations

from typing import Dict, List, Tuple

INT32_BYTES = 4


def rf_total(layer: Dict) -> int:
    """Dendritic inputs per neuron: the receptive field, plus the column's
    own Q previous outputs for a recurrent layer."""
    return int(layer["rf_size"]) + (int(layer["n_neurons"])
                                    if layer.get("recurrent") else 0)


def bank_ops(c: int, b: int, q: int, r: int, t: int) -> int:
    return 2 * c * b * q * r * t


def bank_bytes(c: int, b: int, q: int, r: int) -> int:
    return INT32_BYTES * (c * b * r + c * q * r + c * b * q)


def layer_calls(config: Dict) -> List[Tuple[int, int]]:
    """(ops, bytes) of each layer's bank call in one engine step."""
    b, t = int(config["n_slots"]), int(config["t_steps"])
    calls = []
    for layer in config["layers"]:
        c, q, r = int(layer["n_columns"]), int(layer["n_neurons"]), rf_total(layer)
        calls.append((bank_ops(c, b, q, r, t), bank_bytes(c, b, q, r)))
    return calls


def ops_per_volley(config: Dict) -> int:
    """Dense RNL operations one served volley needs, over every layer."""
    t = int(config["t_steps"])
    return sum(bank_ops(int(lay["n_columns"]), 1, int(lay["n_neurons"]),
                        rf_total(lay), t) for lay in config["layers"])


def least_seconds(calls: List[Tuple[int, int]], peak: Dict) -> float:
    """The least time the chip could take for these calls: each call bound
    by the larger of its operations over the compute peak and its bytes over
    the memory bandwidth."""
    return sum(max(ops / peak["int8_ops_per_s"],
                   nbytes / peak["hbm_bytes_per_s"]) for ops, nbytes in calls)
