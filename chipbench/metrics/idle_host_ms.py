"""idle_host_ms.<variant>: device idle time per step while the host was in
the engine step's own work: ``tnn.step`` outside its ``tnn.measure``,
``tnn.h2d`` and ``tnn.fetch`` spans (admit, batch build, resolve, dispatch,
retire, snapshot). ``harness/spans.py``; None without the program's spans."""

from harness import spans


def read(run):
    split = spans.idle_split(run.trace)
    return None if split is None else split["host"]
