"""Simulated events per second of the window, in millions (host clock).

One event is one flit row of one stream under one design point; the count
per report comes from the cell's shapes (``perfbench.work``), and the rate
is over every report completed and the window's whole length."""


def read(run):
    return run.reports * run.events_per_report / run.window_s / 1e6
