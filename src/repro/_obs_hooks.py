"""Zero-cost observability hook slots (DESIGN.md §14).

This module is the ONLY thing production code imports for telemetry.  It
holds one mutable slot, ``SINK`` — ``None`` by default — that
``repro.obs`` installs a collector into while a ``collect()`` /
``tracing()`` / ``profiling()`` context is active.  With the slot empty
every probe is a single attribute test against ``None`` executed in
Python OUTSIDE any traced computation, so the traced jaxpr of every
kernel entry point is byte-identical whether ``repro.obs`` is imported,
active, or absent (asserted in ``tests/test_obs.py``).

Deliberately dependency-free: importing this module never imports
``repro.obs`` (nor jax), so the hot path carries no observability code
until someone actually turns it on.
"""

from __future__ import annotations

__all__ = ["SINK", "TAP", "active", "capturing", "event", "span", "tap"]

# The installed sink (repro.obs.probes._Sink) or None.  Probes read this
# once per call; repro.obs flips it when the first collector activates.
SINK = None

# The installed traffic tap (repro.obs.capture._Tap) or None.  A separate
# slot from SINK because tap payloads carry ARRAYS (weights, KV slices,
# gradients), not the JSON-safe scalars the probe sink expects.  Same
# zero-cost contract: with the slot empty a tap site is one None test.
TAP = None


class _NullSpan:
    """No-op context manager returned while no sink is installed."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def active() -> bool:
    """True while at least one collector (registry or tracer) is active.

    The profiler consumer (``repro.obs.profiling``) alone leaves this
    False: it records spans only, so the per-link telemetry this guards
    (events and the ``int()`` reads that fill them) stays off in a
    profiled run, which then does the work an unprofiled one does."""
    s = SINK
    return s is not None and s.collecting


def span(kind: str, **data):
    """A context manager around one probe span (no-op when inactive).

    ``kind`` names the probe point (e.g. ``"kernel.dispatch"``); ``data``
    carries JSON-safe scalars only — probe sites fire during jax tracing
    too, so values must never be traced arrays.
    """
    s = SINK
    return _NULL_SPAN if s is None else s.span(kind, data)


def event(kind: str, **data) -> None:
    """Fire one instant probe event (no-op when inactive)."""
    s = SINK
    if s is not None:
        s.event(kind, data)


def capturing() -> bool:
    """True while at least one traffic-capture session is active."""
    return TAP is not None


def tap(kind: str, **payload) -> None:
    """Offer tensors at a traffic-tap site (no-op when no capture active).

    ``kind`` names the tap point (e.g. ``"serve.kv"``); ``payload`` may
    carry jax arrays or pytrees of them.  Tap sites inside jitted
    functions fire with tracers during tracing — the installed tap drops
    those whole-payload (it performs NO jax operations on them), so the
    traced jaxpr stays byte-identical whether capture is absent,
    installed, or active (tests/test_capture.py pins this).
    """
    t = TAP
    if t is not None:
        t.tap(kind, payload)
