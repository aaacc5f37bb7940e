#!/usr/bin/env python3
"""Readings of the controls at a cell's own size.

    python3 chipbench/controls.py --workload <cell> --seeds 1,2,3 \\
        [--kinds float32_clock,reverse_ties]

For each seed, builds the cell's inputs as a run does and puts the
reference itself, computed as each control says
(`reference.des.CONTROLS`), in the program's place; prints one JSON line
per seed and control with the
numbers the run would compare.  A benchmark run never runs this: it gives
the upper readings from which `PERF.md` sets each limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from chipbench import run as harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default="float32_clock,reverse_ties")
    args = ap.parse_args()
    sys.path.insert(1, os.path.join(harness.ROOT, "src"))

    from chipbench import registry
    from repro.core import SimOptions

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    _, cfg, traffic = harness.cell_files(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        work = registry.load("units", traffic["unit"]).Unit(
            cfg, traffic, seed, SimOptions(use_kernel=True), harness.Spans())
        for kind in args.kinds.split(","):
            t0 = time.perf_counter()
            counts = work.control(0, kind)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": kind, "checks": counts,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
