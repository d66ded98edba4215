"""setup_s: seconds from process start to the window's start (JAX start-up,
volley pool, weights, engine, compilation or its cache, warm-up steps)."""


def read(run):
    return run.setup_s
