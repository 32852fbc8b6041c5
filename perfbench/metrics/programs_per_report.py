"""Device program executions per traced report: the number
of dispatches the host pays for one report."""


def read(run):
    t = run.trace
    if t is None or not t.programs:
        return None
    return t.programs / t.reports
