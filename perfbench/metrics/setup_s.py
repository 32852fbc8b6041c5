"""Set-up: process start to the start of the window (host clock).

Loading, weights or traffic, and the warm-up that compiles (or loads from
the compilation cache) every program the window runs."""


def read(run):
    return run.setup_s
