"""idle_outside_ms.<variant>: device idle time per step outside every
``tnn.step`` span: the load generator's loop, ``submit``, open-loop waits,
collections. ``harness/spans.py``; None without the program's spans."""

from harness import spans


def read(run):
    split = spans.idle_split(run.trace)
    return None if split is None else split["outside"]
