"""step_ms.<variant>: mean of the benchmark's own span around each engine
``step()`` call, over the steps inside the traced slice."""


def read(run):
    steps = run.slice_steps()
    if len(steps) == 0:
        return None
    return 1e3 * float((steps[:, 1] - steps[:, 0]).mean())
