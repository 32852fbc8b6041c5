import os
import sys
import types

# Tests must see the real (single) CPU device: a test that needs several
# host devices sets the flag in its own subprocess (tests/test_distributed.py).
assert "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")

try:
    from hypothesis import HealthCheck, settings

    settings.register_profile(
        "repro",
        max_examples=25,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    settings.load_profile("repro")
except ModuleNotFoundError:
    # hypothesis is optional: property tests auto-skip, everything else runs.
    # A stub module is installed so `from hypothesis import given` (and
    # `strategies as st`) in test modules import cleanly; the @given
    # decorator replaces the test body with a skip.
    import pytest

    def _given(*_args, **_kwargs):
        def decorate(_fn):
            def skipper(*a, **k):
                pytest.skip("hypothesis not installed")

            # deliberately no functools.wraps: pytest must see the (*a, **k)
            # signature, not the test's hypothesis-provided parameters
            skipper.__name__ = getattr(_fn, "__name__", "hypothesis_test")
            skipper.__doc__ = getattr(_fn, "__doc__", None)
            return skipper

        return decorate

    def _strategy_stub(*_args, **_kwargs):
        return None

    _st = types.ModuleType("hypothesis.strategies")
    for _name in (
        "integers", "sampled_from", "lists", "floats", "booleans", "text",
        "tuples", "one_of", "just", "composite", "binary",
    ):
        setattr(_st, _name, _strategy_stub)

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.strategies = _st
    _hyp.settings = _strategy_stub
    _hyp.HealthCheck = types.SimpleNamespace(too_slow=None)
    _hyp.assume = _strategy_stub
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st


def pytest_configure(config):
    # register the marker so pytest does not warn about it; no run
    # deselects it
    config.addinivalue_line(
        "markers", "slow: a test that takes tens of seconds or more"
    )
