"""gen_late_ms_p99: 99th percentile of how late the load generator sent a
request (``submit`` returned minus due time), over the requests due inside
the traced slice (open loop only)."""

from harness import runner


def read(run):
    if run.slice is None or run.cell.traffic["loop"] != "open":
        return None
    a, b = run.slice
    late = [r.sent - r.due for r in run.window_requests() if a <= r.due <= b]
    return 1e3 * runner.percentile(late, 99) if late else None
