"""idle_measure_ms.<variant>: device idle time per step while the host
measured the batch for the engine policy (the ``tnn.measure`` span: density,
per-stage densities, layer 0's active lines). ``harness/spans.py``; None
without the program's spans."""

from harness import spans


def read(run):
    split = spans.idle_split(run.trace)
    return None if split is None else split["measure"]
