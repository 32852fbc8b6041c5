"""Device busy time per traced report outside the ``bt_count_links`` and
``quantize_egress`` kernels: the adapters' device work (the fleet's flow
slices, the dispatch's grouping gather) and the fabric expansion (send
order, queue gathers, flit assembly, padding)."""

from perfbench.trace import QUANTIZE_PROGRAM

LINKS_PROGRAM = "_bt_count_links"


def read(run):
    t = run.trace
    if t is None or not t.reports:
        return None
    rest = (t.busy_s - t.kernel_seconds(LINKS_PROGRAM)
            - t.kernel_seconds(QUANTIZE_PROGRAM))
    return 1e3 * rest / t.reports
