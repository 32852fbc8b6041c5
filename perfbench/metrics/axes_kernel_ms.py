"""Device time per report of the multi-axis BT kernel (``bt_axes``) when
it measures a stream, outside the fused transmit path."""

from perfbench.trace import AXES_PROGRAM


def read(run):
    t = run.trace
    if t is None:
        return None
    s = t.kernel_seconds(AXES_PROGRAM)
    return None if s <= 0 else 1e3 * s / t.reports
