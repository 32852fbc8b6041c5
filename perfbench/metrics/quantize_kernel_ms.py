"""Device time per traced report of the int8 egress quantizer kernel."""

from perfbench.trace import QUANTIZE_PROGRAM


def read(run):
    t = run.trace
    if t is None:
        return None
    s = t.kernel_seconds(QUANTIZE_PROGRAM)
    return None if s <= 0 else 1e3 * s / t.reports
