"""Device time per report of the fused sort + pack + BT kernel that
``TxPipeline`` launches for sorted framings (``bt_axes`` inside the
``psu_stream`` program)."""

from perfbench.trace import PSU_PROGRAM


def read(run):
    t = run.trace
    if t is None:
        return None
    s = t.kernel_seconds(PSU_PROGRAM)
    return None if s <= 0 else 1e3 * s / t.reports
