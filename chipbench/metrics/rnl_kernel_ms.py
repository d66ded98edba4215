"""rnl_kernel_ms: device time of the RNL bank kernel per engine step: the
summed durations of its events in the traced window (both layers' calls),
over the ``chipbench.step`` spans in that window."""

from harness import trace

#: the kernel as the trace names it today: the ``pallas_call`` made by
#: ``kernels/rnl_neuron.rnl_fire_times_layer``, one per layer
#: (``rnl_fire_times_layer.2``, ``rnl_fire_times_layer.3``)
KERNEL = r"^rnl_fire_times_layer(\.\d+)?$"


def read(run):
    if run.trace is None:
        return None
    w = trace.window(run.trace)
    if w is None:
        return None
    a, b, n_steps = w
    ns = trace.kernel_ns(run.trace, KERNEL, a, b)
    if ns <= 0 or n_steps == 0:
        return None
    return ns / 1e6 / n_steps
