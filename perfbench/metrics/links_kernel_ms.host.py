"""Device time per traced report of the BT kernel that ``bt_count_links``
launches over a fabric's distinct link queues (the Pallas kernel inside
the ``_bt_count_links`` program), in a cell that moves ``flit_rate.host``."""

LINKS_PROGRAM = "_bt_count_links"


def read(run):
    t = run.trace
    if t is None:
        return None
    s = t.kernel_seconds(LINKS_PROGRAM)
    return None if s <= 0 else 1e3 * s / t.reports
