"""device_idle_pct.<variant>: share of the traced window in which no
operation ran on the device: 100 x (1 - union of operation intervals over
the window), averaged over the devices used."""

from harness import trace


def read(run):
    if run.trace is None:
        return None
    w = trace.window(run.trace)
    if w is None or not any(run.trace.devices):
        return None
    a, b, _ = w
    return 100.0 * (1.0 - trace.busy_ns(run.trace, a, b) / (b - a))
