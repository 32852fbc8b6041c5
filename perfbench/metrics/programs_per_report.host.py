"""``programs_per_report`` in a host-bound cell, moving ``flit_rate.host``."""

from perfbench.metrics.programs_per_report import read  # noqa: F401
