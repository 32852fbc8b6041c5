#!/usr/bin/env python3
"""Readings that the comparison's limits are set from.

    python3 perfbench/control.py --workload <name> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: the cell's set-up and a short window at the
cell's own size, then the check (the program's readings), then the control
put in the program's place and checked the same way (the control's
readings).  The control is the reference one step of precision down: the
float32 quantizer computed in bfloat16, or 8-bit wire bytes cut to their
4 high bits.  One JSON line per seed.  Needs the chip, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import numpy as np

    from perfbench import harness

    harness.enable_compile_cache()
    bench = harness.load_benchmark()
    entry, config, traffic = harness.find_cell(bench, args.workload)
    harness.check_device(int(entry["chips"]))
    driver = harness.load_driver(traffic)
    span = harness.span_factory(False)
    for seed in args.seeds:
        t0 = time.time()
        s = harness.derive_seed(seed)
        cell = driver.setup(config, traffic, s, span)
        lat, window_s = harness.run_window(cell, args.seconds, span)
        program, wrong = cell.check(np.random.default_rng(s + 1))
        control, c_wrong = cell.control()
        print(json.dumps({
            "seed": seed, "reports": len(lat), "window_s": window_s,
            "program": {c.name: c.value for c in program},
            "program_failed": wrong,
            "control": {c.name: c.value for c in control},
            "control_failed": c_wrong,
            "seconds": time.time() - t0,
        }), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
