"""volleys_per_s: volleys of the requests that retired in the window, over
the window's seconds (from its start to the end of its last step)."""


def read(run):
    span = run.t_end - run.t0
    if span <= 0:
        return None
    done = sum(r.length for r in run.requests
               if run.t0 < r.done <= run.t_end)
    return done / span
