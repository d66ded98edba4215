"""idle_transfer_ms.<variant>: device idle time per step while the host
placed the batch on the device or waited on the step's outputs (the
``tnn.h2d`` and ``tnn.fetch`` spans). ``harness/spans.py``; None without the
program's spans."""

from harness import spans


def read(run):
    split = spans.idle_split(run.trace)
    return None if split is None else split["transfer"]
