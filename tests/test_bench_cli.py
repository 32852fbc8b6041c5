"""The report CLI (``python -m benchmarks.run``): its registry, its error
path, and the ``(name, derived)`` row contract of its modules."""

import importlib
import os
import pathlib
import subprocess
import sys

import pytest

_REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_REPO))
from benchmarks.run import MODULES  # noqa: E402


def _cli(*args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([str(_REPO / "src"), str(_REPO)])
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args],
        capture_output=True, text=True, env=env, cwd=str(_REPO), timeout=120,
    )


def test_list_prints_the_registry():
    out = _cli("--list")
    assert out.returncode == 0, out.stderr
    assert tuple(out.stdout.split()) == MODULES


@pytest.mark.parametrize("name", MODULES)
def test_registered_module_has_run(name):
    mod = importlib.import_module(f"benchmarks.{name}")
    assert callable(mod.run)


def test_unknown_module_exits_nonzero_listing_valid_names():
    out = _cli("table1_bt", "nonsense")
    assert out.returncode != 0
    assert "'nonsense'" in out.stderr
    assert all(name in out.stderr for name in MODULES)
    assert out.stdout == ""  # refused before any module ran


@pytest.mark.parametrize("name", ["fig5_area", "fig7_power"])
def test_module_rows_are_unique_name_derived_pairs(name):
    mod = importlib.import_module(f"benchmarks.{name}")
    rows = mod.run(**getattr(mod, "TINY_KWARGS", {}))
    assert rows
    for row in rows:
        assert isinstance(row, tuple) and len(row) == 2
        assert all(isinstance(field, str) for field in row)
    names = [n for n, _ in rows]
    assert len(names) == len(set(names))
