"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data. A cell names a configuration (its file
of sizes), a traffic mix (``traffic/<name>.json``, parameters that one
driver reads; the file's ``driver`` key picks ``drivers/<driver>.py``)
and, through ``per_layer``, the readers ``metrics/<name>.py``. A later
change adds a cell, a configuration, a mix, a driver or a metric as new
files and new entries and edits nothing that is here.

The last line of standard output is one JSON object; the numbers that
decided ``correct`` are its last key and the last lines of standard
error, each beside its limit. It measures on a TPU and nowhere else.
"""

from __future__ import annotations

import time

T_START = time.time()       # set-up counts from here

import argparse             # noqa: E402
import importlib.util       # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# drivers and readers import the yardstick (flops, reference,
# trace_reduce, loadgen) by these names, and the program from the root
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A driver or a metric reader, found by its file's name."""
    name = "bench_" + os.path.splitext(os.path.basename(path))[0].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(root: str, workload: str) -> dict:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = dict(cells[workload])
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    home = os.path.join(root, bench["paths"][0])
    cell["config_file"] = load_json(os.path.join(root, config["file"]))
    cell["traffic_file"] = load_json(
        os.path.join(home, "traffic", cell["traffic"] + ".json"))
    cell["home"] = home
    cell["root"] = root

    def reported(metric):
        return workload in metric.get("workloads", [workload])
    cell["end_to_end"] = [m for m in bench["end_to_end"] if reported(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if reported(m)]
    return cell


def place_compile_cache(root: str) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if the caller set it, else a fixed
    directory inside the checkout: the path is part of the cache's key."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = os.path.join(root, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


def device_facts(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if require_tpu and (facts["platform"] != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"the benchmark measures on a TPU with {chips} chip(s); jax "
            f"found {facts['count']} x {facts['platform']!r}")
    return facts


def read_per_layer(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric is a reader of its own; one that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for metric in cell["per_layer"]:
        reader = load_module(os.path.join(
            cell["home"], "metrics", metric["name"] + ".py"))
        value = reader.read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def judge(checks: list) -> bool:
    return all(c["value"] <= c["limit"] for c in checks)


def compared(checks: list) -> dict:
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True) -> dict:
    """Everything but the command line; a test calls it with
    ``require_tpu=False`` to drive a run without the look for a chip."""
    cell = load_cell(root, workload)
    place_compile_cache(root)
    device = device_facts(cell["chips"], require_tpu)
    trace_dir = os.path.join(root, ".bench_trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    driver = load_module(os.path.join(
        cell["home"], "drivers", cell["traffic_file"]["driver"] + ".py"))
    res = driver.run(cell, seed=seed, seconds=seconds,
                     trace_dir=trace_dir if trace else None,
                     t_start=T_START)
    device["memory_peak_bytes"] = int(res["memory_peak_bytes"])
    names = {m["name"]: m for m in cell["end_to_end"]}
    end_to_end = {k: {"value": float(v), "unit": names[k]["unit"]}
                  for k, v in res["end_to_end"].items() if k in names}
    line = {"correct": judge(res["checks"]),
            "attempted": int(res["attempted"]),
            "failed": int(res["failed"])}
    if trace:
        from trace_reduce import breakdown, reduce_trace
        reduced = reduce_trace(trace_dir, res.get("trace_skip_first", 0))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        from flops import peaks
        ctx = {"cell": cell, "trace": reduced,
               "counters": res.get("counters", {}),
               "end_to_end": res["end_to_end"],
               "peak": peaks(device["kind"]) if require_tpu else None,
               "device": device}
        line["metrics"] = read_per_layer(cell, ctx)
        line["breakdown"] = breakdown(reduced)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        line["metrics"] = end_to_end
    line["device"] = device
    line["info"] = res.get("info", {})
    line["compared"] = compared(res["checks"])
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace))
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        print(f"compared {name} {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
