"""The controls of ``correct``, on the chip at a cell's own size:

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 \\
        --which sound,fp8,... [--seconds s]

For each seed, in one process, it prints what the cell's comparison
reads for the program ('sound') and when the reference in the precision
below the stated one, or a planted fault, stands in the program's place
(the driver's ``control``), each number beside its limit and whether
``correct`` comes out true, by run.py's own rule. The benchmark's own
runs never run this; PERF.md keeps the readings, and
tests/benchmark_cells keeps the same at a toy size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run as harness


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--which", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    cell = harness.load_cell(harness.ROOT, args.workload)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell["seconds"] = args.seconds or bench["run_seconds"]
    harness.place_compile_cache(harness.ROOT)
    harness.device_facts(cell["chips"])
    driver = harness.load_module(os.path.join(
        cell["home"], "drivers", cell["traffic_file"]["driver"] + ".py"))
    for seed in (int(s) for s in args.seeds.split(",")):
        got = driver.control(cell, seed, args.which.split(","))
        info = got.pop("info", {})
        for name, checks in got.items():
            print(json.dumps({
                "workload": args.workload, "seed": seed, "stand_in": name,
                "correct": harness.judge(checks),
                "compared": harness.compared(checks)}), flush=True)
        if info:
            print(json.dumps({"seed": seed, "info": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
