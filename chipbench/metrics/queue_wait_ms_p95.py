"""queue_wait_ms_p95: 95th percentile of the wait from a request's due time
to the start of the first step that served it, over the requests first
served inside the traced slice (open loop only)."""

import math

from harness import runner


def read(run):
    if run.slice is None or run.cell.traffic["loop"] != "open":
        return None
    a, b = run.slice
    waits = [r.started - r.due for r in run.window_requests()
             if not math.isnan(r.started) and a <= r.started <= b]
    return 1e3 * runner.percentile(waits, 95) if waits else None
