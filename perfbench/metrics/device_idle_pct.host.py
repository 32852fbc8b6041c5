"""``device_idle_pct`` in a host-bound cell, moving ``flit_rate.host``."""

from perfbench.metrics.device_idle_pct import read  # noqa: F401
