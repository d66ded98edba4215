"""rnl_roofline: the RNL bank kernel's share of its roofline, in %: the least
time the chip could take for one step's bank calls (each layer's call bound
by the larger of its operations over the int8 peak and its bytes over the
HBM bandwidth; ``harness/work.py``), over the kernel's measured time per
step (``rnl_kernel_ms``)."""

from harness import spec, work


def read(run):
    kernel_ms = spec.load_reader("rnl_kernel_ms")(run)
    if kernel_ms is None or not run.peak:
        return None
    least_s = work.least_seconds(work.layer_calls(run.config), run.peak)
    return 100.0 * least_s / (kernel_ms / 1e3)
