"""step_mfu: the whole gamma cycle's share of the chip's int8 peak, in %:
dense RNL operations per served volley (``harness/work.py``) times the
volleys the program served per second over the traced slice's steps, over
the peak."""

from harness import work


def read(run):
    mask = run.slice_mask()
    if mask.sum() < 2 or not run.peak:
        return None
    steps = run.steps[mask]
    rate = run.step_volleys()[mask].sum() / (steps[-1, 1] - steps[0, 0])
    return (100.0 * work.ops_per_volley(run.config) * rate
            / run.peak["int8_ops_per_s"])
