"""The ``bt_count_links`` kernel's share of its HBM roofline.

The least time is the launches' least bytes (each distinct queue's uint8
flit rows read once and its BT row written once, from the cell's routing
plan: ``work["links_wire_bytes"]`` per report) over the chip's published
HBM bandwidth; the share is that over the kernel's device time.  HBM is
the bound named because the chip publishes no vector-unit peak."""

LINKS_PROGRAM = "_bt_count_links"


def read(run):
    t = run.trace
    nbytes = run.work.get("links_wire_bytes")
    if t is None or not nbytes or not run.peaks:
        return None
    s = t.kernel_seconds(LINKS_PROGRAM)
    if s <= 0:
        return None
    least = t.reports * nbytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
