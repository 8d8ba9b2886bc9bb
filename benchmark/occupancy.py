"""Whether the device was idle with a request waiting, and how full the
buckets ran: one served profile, read through ``host_spans``.

    python3 benchmark/occupancy.py <trace dir or .xplane.pb> [skip_first]

prints the split of the device's idle time, the pending part by the
phase of whoever held the batch, the fill, and the ten longest gaps
with their split.

The program says when it had work. Each ``serve.execute`` phase carries
``oldest_wait_us``, the time from the earliest arrival among the batch's
requests to the worker taking the batch, so on the profiler's clock
``[start - oldest_wait_us, end of the batch's tpu_model.dispatch]`` is
an interval in which the engine held a request whose work had not been
handed to the device. A device gap over ``host_spans.GAP_FLOOR_NS`` is
**pending** where some batch's interval covers it and **starved** where
none does: pending idle time is what the gate, ``max_wait_ms`` or the
worker's host work cost; starved idle time is the traffic's. Batches
marked ``retry`` are left out, as ``host_spans.batches`` leaves them.

The pending time is charged to whoever held the batch. Until the
batcher puts it into the dispatch queue (``dispatch_wait_us`` over
``rows`` before the phase's start) the batch is in the batcher's hands,
and the time goes to the batcher's innermost phase (``serve.collect``,
``serve.token_wait``, ``serve.decode``, or none of them open); from then
on a worker could run it, and the time goes to the worker's
(``serve.idle``, ``serve.respond``, ``tpu_model.pad``,
``tpu_model.dispatch``, ..., or none). The batcher has long gone back to
collecting the next batch by then, so charging it too would put the
worker's time under ``serve.collect`` (161 of 170 ms in the first
profile of the GPT-2-XL cell read so). ``serve.collect`` is not one of
the program's ``HOST_PHASES``, so ``host_spans.load`` does not read it:
its events come from the same file through ``host_spans.host_phases``.

The fill is real rows over bucket rows of the window's
``tpu_model.dispatch`` phases, which carry both. A step takes what its
bucket takes whatever it holds, and the readers of a step's share of a
peak count real rows, so such a share over the fill is the full
bucket's.

Everything is silent (None) where ``host_spans.for_run`` is, and where
the program does not say these things: the parent of the change that
brought them has no ``ARRIVAL_WAITS``, no ``oldest_wait_us`` and no
``bucket``.
"""

from __future__ import annotations

import functools
import os
import sys
from collections import defaultdict

import host_spans

BATCHER = ("serve.collect", "serve.token_wait", "serve.decode")
NONE = "none"


def arrival_names():
    """The names of the program's waits for arrivals, or None where the
    program has none."""
    try:
        from mmlspark_tpu.core.trace import ARRIVAL_WAITS
    except ImportError:
        return None
    return tuple(ARRIVAL_WAITS)


@functools.lru_cache(maxsize=2)
def arrival_phases(path: str):
    """The ``ARRIVAL_WAITS`` events of one profile, threads numbered as
    ``host_spans.load`` numbers them; None where the program has no
    such names."""
    from jax.profiler import ProfileData
    from trace_reduce import find_trace
    names = arrival_names()
    if names is None:
        return None
    if os.path.isdir(path):
        path = find_trace(path)
    return host_spans.host_phases(ProfileData.from_file(path), names)


def for_run(ctx: dict):
    """``host_spans.for_run`` with the arrival waits of the same profile
    under ``"arrivals"`` (spans a test put into the context bring their
    own), or None."""
    spans = host_spans.for_run(ctx)
    if not spans:
        return None
    if ctx.get("host_spans") is not None:
        return {"arrivals": [], **spans}
    cell = ctx.get("cell") or {}
    try:
        arrivals = arrival_phases(os.path.join(
            cell.get("root", ""), ".bench_trace", cell.get("name", "")))
    except (FileNotFoundError, ValueError):
        return None
    return None if arrivals is None else {**spans, "arrivals": arrivals}


# ------------------------------------------------------------- what it offers

def pending_intervals(spans: dict) -> list:
    """(from, queued, to) in nanoseconds, a batch each: from its oldest
    request's arrival, through the batcher putting it into the dispatch
    queue, to the end of its last ``tpu_model.dispatch`` (to the worker
    taking it, for a batch the profile holds no dispatch of). None
    where no batch says how long its oldest request waited."""
    handed = {}
    for p in spans["phases"]:
        st = p["stats"]
        if p["name"] == "tpu_model.dispatch" and "batch" in st \
                and not st.get("retry"):
            handed[st["batch"]] = max(p["end"], handed.get(st["batch"], 0))
    out = []
    for p in spans["phases"]:
        st = p["stats"]
        if p["name"] == "serve.execute" and "oldest_wait_us" in st \
                and not st.get("retry"):
            lay_us = st["dispatch_wait_us"] / st["rows"]    # a sum over rows
            out.append((p["start"] - st["oldest_wait_us"] * 1e3,
                        p["start"] - lay_us * 1e3,
                        max(handed.get(st["batch"], 0), p["start"])))
    return out or None


def _innermost(phases: list, a: float, b: float) -> list:
    """The name of the innermost phase covering (a, b) on each thread
    that has one; ``phases`` in order of start, then longest first."""
    inner = {}
    for p in phases:
        if p["start"] <= a and p["end"] >= b:
            inner[p["thread"]] = p["name"]
    return list(inner.values())


def split(spans: dict):
    """The device's gaps over the floor, split. A dict: ``window_ns``;
    ``idle_ns``, ``pending_ns`` and ``starved_ns`` (the last two add up
    to the first); ``by_batcher``, the pending time in which no batch
    had reached the dispatch queue, by the batcher's innermost phase,
    and ``by_worker``, the rest of it, by the worker's (a thread each:
    two workers in phases at once are both charged); ``gaps``, each as
    (start_ns, length_ns, pending_ns, by_batcher, by_worker). None
    where the program does not say when it held a request."""
    waiting = pending_intervals(spans)
    if waiting is None:
        return None
    every = sorted(spans["phases"] + spans["arrivals"],
                   key=lambda p: (p["start"], -p["end"]))
    sides = {"batcher": [p for p in every if p["name"] in BATCHER],
             "worker": [p for p in every if p["name"] not in BATCHER]}
    total = {side: defaultdict(float) for side in sides}
    gaps = []
    for g0, g1 in spans["gaps"]:
        if g1 - g0 <= host_spans.GAP_FLOOR_NS:
            continue
        over = {side: [p for p in ps if p["start"] < g1 and p["end"] > g0]
                for side, ps in sides.items()}
        held = [w for w in waiting if w[0] < g1 and w[2] > g0]
        cuts = sorted({g0, g1} | {t for w in held for t in w if g0 < t < g1}
                      | {t for ps in over.values() for p in ps
                         for t in (p["start"], p["end"]) if g0 < t < g1})
        pending = 0.0
        cover = {side: defaultdict(float) for side in sides}
        for a, b in zip(cuts, cuts[1:]):
            covering = [w for w in held if w[0] <= a and w[2] >= b]
            if not covering:
                continue
            pending += b - a
            side = "worker" if any(w[1] <= a for w in covering) \
                else "batcher"
            for name in _innermost(over[side], a, b) or [NONE]:
                cover[side][name] += b - a
        for side in sides:
            for name, t in cover[side].items():
                total[side][name] += t
        gaps.append((g0, g1 - g0, pending, dict(cover["batcher"]),
                     dict(cover["worker"])))
    idle = sum(g[1] for g in gaps)
    pending = sum(g[2] for g in gaps)
    w0, w1 = spans["window"]
    return {"window_ns": w1 - w0, "idle_ns": idle, "pending_ns": pending,
            "starved_ns": idle - pending,
            "by_batcher": dict(total["batcher"]),
            "by_worker": dict(total["worker"]), "gaps": gaps}


def fill_percent(spans: dict):
    """Real rows over bucket rows of the window's executions, in
    percent: of the ``tpu_model.dispatch`` phases that end inside the
    window, or within the clock check's limit before it (an execution
    starts as its dispatch ends, and the window starts with one). None
    where no such phase says its bucket."""
    w0, w1 = spans["window"]
    rows = bucket = 0
    for p in spans["phases"]:
        st = p["stats"]
        if p["name"] == "tpu_model.dispatch" and "bucket" in st \
                and not st.get("retry") \
                and w0 - host_spans.CLOCK_LIMIT_MS * 1e6 <= p["end"] <= w1:
            rows += st["rows"]
            bucket += st["bucket"]
    return 100.0 * rows / bucket if bucket else None


def _idle_percent(ctx: dict, part: str):
    spans = for_run(ctx)
    got = spans and split(spans)
    return got and 100.0 * got[part] / got["window_ns"]


def read_pending(ctx: dict):
    """The reader of ``device_idle_serve_pending``."""
    return _idle_percent(ctx, "pending_ns")


def read_starved(ctx: dict):
    """The reader of ``device_idle_serve_starved``."""
    return _idle_percent(ctx, "starved_ns")


def read_fill(ctx: dict):
    """The reader of ``serve_bucket_fill``."""
    spans = for_run(ctx)
    return spans and fill_percent(spans)


# ------------------------------------------------------------------ printing

def _by_name(cover: dict) -> str:
    return ", ".join(f"{n} {t / 1e6:.3f}" for n, t in sorted(
        cover.items(), key=lambda kv: -kv[1])) or "nothing"


def report(spans: dict) -> str:
    got, w0 = split(spans), spans["window"][0]
    if got is None:
        return "no serve.execute phase says how long its oldest request " \
               "waited: the program does not say when it held one"
    window = got["window_ns"]
    out = [f"window {window / 1e9:.3f} s, {len(got['gaps'])} device gaps "
           f"over {host_spans.GAP_FLOOR_NS / 1e6} ms, "
           f"{got['idle_ns'] / 1e6:.3f} ms idle = "
           f"{100.0 * got['idle_ns'] / window:.3f} % of the window"]
    for part in ("pending", "starved"):
        out.append(f"{part} {got[part + '_ns'] / 1e6:.3f} ms = "
                   f"{100.0 * got[part + '_ns'] / window:.3f} %")
    out.append("pending in the batcher's hands, by its phase, ms: "
               + _by_name(got["by_batcher"]))
    out.append("pending in the dispatch queue or a worker's hands, by the "
               "worker's phase, ms: " + _by_name(got["by_worker"]))
    out.append(f"bucket fill {fill_percent(spans)} %")
    for g0, length, pending, batcher, worker in sorted(
            got["gaps"], key=lambda g: -g[1])[:10]:
        out.append(f"  gap at {(g0 - w0) / 1e9:.3f} s, {length / 1e6:.3f} ms: "
                   f"starved {(length - pending) / 1e6:.3f}, pending "
                   f"{pending / 1e6:.3f} (batcher: {_by_name(batcher)}; "
                   f"worker: {_by_name(worker)})")
    return "\n".join(out)


def main(argv) -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [p for p in (here, os.path.dirname(here))
                    if p not in sys.path]
    spans = host_spans.load(argv[1])
    arrivals = arrival_phases(argv[1])
    if spans is None or arrivals is None:
        print("no host phases, no device plane or no arrival waits: "
              "nothing to split")
        return 1
    spans = host_spans.skip_first(spans,
                                  int(argv[2]) if len(argv) > 2 else 0)
    print(f"clock check {host_spans.clock_gap_ms(spans)} ms (limit "
          f"{host_spans.CLOCK_LIMIT_MS})")
    print(report({**spans, "arrivals": arrivals}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
