"""request_latency_p95_ms: 95th percentile, over every request due in the
window, of retirement time minus due time. A request that never retired
counts as retiring at the end of the run's last step, so it misses any limit
a retired request meets."""

import math

from harness import runner


def read(run):
    reqs = run.window_requests()
    if not reqs:
        return None
    last = float(run.steps[-1, 1])
    lat = [(r.done if not math.isnan(r.done) else last) - r.due for r in reqs]
    return 1e3 * runner.percentile(lat, 95)
