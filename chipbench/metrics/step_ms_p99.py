"""step_ms_p99.<variant>: 99th percentile of the benchmark's span around
each engine ``step()`` call in the traced slice (snapshot stalls show here)."""

from harness import runner


def read(run):
    steps = run.slice_steps()
    if len(steps) == 0:
        return None
    return 1e3 * runner.percentile(steps[:, 1] - steps[:, 0], 99)
