"""snapshot_stall_ms: mean duration of the engine's ``tnn.snapshot`` spans
in the traced window: the serve thread's copy of the weights to the host and
its wait on the previous save. ``harness/spans.py``; None without them."""

from harness import spans


def read(run):
    return spans.snapshot_ms(run.trace)
