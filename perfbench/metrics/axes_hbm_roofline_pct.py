"""The ``bt_axes`` kernel's share of its HBM roofline.

The least time the chip could take is the launch's least bytes (the
stream's uint8 wire bytes read once, plus its BT table written once,
``perfbench.work.axes_wire_bytes``) over the chip's published HBM
bandwidth; the share is that over the kernel's device time.  HBM is the
bound named because the chip publishes no peak for the vector unit, and
the kernel does no matrix work that counts."""

from perfbench.trace import AXES_PROGRAM


def read(run):
    t = run.trace
    nbytes = run.work.get("axes_wire_bytes")
    if t is None or not nbytes or not run.peaks:
        return None
    s = t.kernel_seconds(AXES_PROGRAM)
    if s <= 0:
        return None
    least = t.reports * nbytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
