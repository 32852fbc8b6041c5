"""``axes_kernel_ms`` in a host-bound cell, moving ``flit_rate.host``."""

from perfbench.metrics.axes_kernel_ms import read  # noqa: F401
