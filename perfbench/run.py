#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It needs the chips the cell names in
``BENCHMARK.json`` and the ``pallas`` kernel backend; without them it exits
non-zero and prints no result.  The last line of standard output is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` with ``--trace 1``), then ``compared``: each
number compared with the reference, beside its limit.  The same numbers are
the last lines of standard error.
"""

from __future__ import annotations

import time

STARTED = time.time()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no program under test: {ROOT}/src/repro is missing",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from perfbench import harness

    try:
        harness.enable_compile_cache()
        line = harness.execute(args, STARTED)
    except harness.BenchError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
