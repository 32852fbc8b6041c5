"""Simulated events per second of the window, in millions, in a cell whose
host dispatch bounds it: ``flit_rate`` read the same way, under a bound of
its own, as such a cell's runs spread wider than a device-bound one's."""

from perfbench.metrics.flit_rate import read  # noqa: F401
