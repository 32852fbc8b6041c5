"""Work counts from shapes, independent of the program's counters.

A simulated event is one flit row of one stream under one design point
(ordering x codec): the unit of work a link-power report prices.
"""

from __future__ import annotations


def flit_rows(values: int, elems: int, lanes: int) -> int:
    """Flit rows of a stream of ``values`` bytes cut into packets of
    ``elems`` bytes (whole packets only) on ``lanes`` byte lanes."""
    if elems % lanes:
        raise ValueError(f"{elems}-byte packets do not fill {lanes} lanes")
    return (values // elems) * (elems // lanes)


def stream_events(values: int, elems: int, lanes: int, designs: int) -> int:
    """Simulated events of one stream measured under ``designs`` points."""
    return flit_rows(values, elems, lanes) * designs


def axes_wire_bytes(values: int, links: int, designs: int) -> int:
    """Least HBM bytes of one measurement launch over a byte stream: the
    stream's uint8 wire bytes read once, plus its int32 (links, designs, 3)
    BT table written once."""
    return values + 4 * 3 * links * designs

