"""The sweep that finds a serving cell's knee, on the chip, once:

    python3 benchmark/knee_sweep.py --workload <name> --rates 2.6,3.0,3.4 \\
        [--seconds 30] [--seed 1]

In one process, for each rate, it writes the rate into the cell's
traffic file (put back as it was at the end), offers one window through the
cell's own driver and prints what the client saw: the latencies, how
many requests were answered inside the window, the engine's counters.
The highest rate at which the queue does not grow (the latencies of
the window's last third are those of its first) is the knee; the cell
runs at four fifths of it. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import run as harness


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import loadgen
    cell = harness.load_cell(harness.ROOT, args.workload)
    harness.place_compile_cache(harness.ROOT)
    harness.device_facts(cell["chips"])
    driver = harness.load_module(os.path.join(
        cell["home"], "drivers", cell["traffic_file"]["driver"] + ".py"))
    path = os.path.join(cell["home"], "traffic", cell["traffic"] + ".json")
    # the load generator reads the mix from the file, so each rate is
    # written there; the cell's own mix is put back when the sweep ends
    with open(path) as f:
        stated = f.read()
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(cell["traffic_file"])
            mix["arrivals"] = {**mix["arrivals"], "rate_per_s": rate}
            with open(path, "w") as f:
                json.dump(mix, f)
            cell["traffic_file"] = mix
            got = driver.serve_window(cell, args.seed, args.seconds)
            result = got["result"]
            lat = loadgen.latencies_ms(result, mix["reply_timeout_s"] * 2e3)
            third = max(1, len(lat) // 3)
            inside = sum(1 for d, s in zip(result["done"], result["status"])
                         if s == 200 and d is not None and d <= args.seconds)
            print(json.dumps({
                "rate_per_s": rate, "offered": len(lat),
                "answered_in_window_per_s": inside / args.seconds,
                "p50_ms": loadgen.percentile(lat, 50),
                "p95_ms": loadgen.percentile(lat, 95),
                "mean_first_third_ms": sum(lat[:third]) / third,
                "mean_last_third_ms": sum(lat[-third:]) / third,
                "unanswered": got["unanswered"],
                "counters": got["counters"]}), flush=True)
            # the next rate brings its own weights up: these have to be gone
            del got, result
            gc.collect()
    finally:
        with open(path, "w") as f:
            f.write(stated)
    return 0


if __name__ == "__main__":
    sys.exit(main())
