"""The program's host phases beside the device rows of one profile.

The program times each host stage of its hot paths with
``mmlspark_tpu.core.trace.phase``, which puts it into the profiler's
file as an event of the ``/host:CPU`` plane named after the stage, with
the stage's attributes as the event's ``stats``. That file is the one
``trace_reduce`` takes the device's busy time from, so host and device
share a clock there and a gap between device executions can be charged
to what the host was doing in it.

    python3 benchmark/host_spans.py <trace dir or .xplane.pb> [skip_first]

prints the request's stages, the host time a batch by phase, and the
table of device gaps by the phase that covers most of each.

The device window is cut by ``trace_reduce``'s rule, restated here in
absolute nanoseconds (``reduce_plane`` returns times from the window's
start): the main program is the one with the most device time, the
window runs from its first counted execution to the end of its last,
busy time is the union of the leaf operations.

Before anything is read the clocks are checked: a host phase that
blocks on the device ends soon after the device does, and never before.
So the median distance from the end of the last execution of the main
program that ended before such a phase did, to the phase's end, has to
be under ``CLOCK_LIMIT_MS`` (a host clock that runs behind makes the
phase end before its execution, and the distance jumps to a whole
period); where it is not, or where the program has no such phases (the
parent of the change that brought them), ``for_run`` gives None and
every reader built on it is silent.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
import statistics
import sys
import warnings
from collections import defaultdict

# a device gap shorter than this is the device's own business
GAP_FLOOR_NS = 500_000
# On a v5e the blocked read ends 3.0 ms after the execution, in the
# serving cell and in the training cell alike (PR 28): the runtime sees
# the program's end 2.0 ms late, then copies the result out. A limit of
# 2 ms, as first asked for, would read that latency as a skew.
CLOCK_LIMIT_MS = 5.0
# phases that end when the device does: the blocked read of a batch's
# outputs, of a chunk's losses, of the last state
BLOCKS_ON_DEVICE = ("tpu_model.readback", "learner.flush_logs",
                    "learner.final_wait")
# ... if it waited at all: a read of what was long ready (a log flush
# one step behind the device) ends at no particular time
BLOCKED_FLOOR_NS = 1_000_000
# a phase in which a thread waits for the worker that feeds the device:
# it covers every gap and explains none
WAITS_FOR_WORKER = ("serve.token_wait",)
UNNAMED = "unnamed"
WAITS = ("queue_wait", "collect_wait", "token_wait", "dispatch_wait")


def stage_names():
    """The names the program gives its host phases, or None where the
    program has no stage clock."""
    try:
        from mmlspark_tpu.core.trace import HOST_PHASES
    except ImportError:
        return None
    return tuple(HOST_PHASES)


def host_phases(data, names) -> list:
    """Events of the host plane that are phases of the program: dicts
    of name, start and end in nanoseconds, the thread (its line's
    number in the plane: the names repeat) and the event's stats."""
    names = set(names)
    out = []
    with warnings.catch_warnings():
        # the first read of an event's stats warns that their type
        # "has no __module__ attribute"
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for thread, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name in names:
                        out.append({"name": ev.name, "start": ev.start_ns,
                                    "end": ev.start_ns + ev.duration_ns,
                                    "thread": thread,
                                    "stats": dict(ev.stats)})
    return sorted(out, key=lambda p: (p["start"], -p["end"]))


def device_window(plane):
    """``trace_reduce.reduce_plane``'s window in absolute nanoseconds,
    with no execution skipped: (executions of the main program, gaps
    between the leaf operations inside the window), or None."""
    from trace_reduce import _leaves
    lines = {ln.name: ln for ln in plane.lines}
    if "XLA Ops" not in lines or "XLA Modules" not in lines:
        return None
    mods = defaultdict(list)
    for ev in lines["XLA Modules"].events:
        mods[re.sub(r"\(\d+\)$", "", ev.name)].append(
            (ev.start_ns, ev.start_ns + ev.duration_ns))
    if not mods:
        return None
    main = max(mods, key=lambda m: sum(e - s for s, e in mods[m]))
    runs = sorted(mods[main])
    w0, w1 = runs[0][0], max(e for _, e in runs)
    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
           for ev in lines["XLA Ops"].events
           if ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1]
    gaps, cursor = [], w0
    for s, e, _ in _leaves(ops):
        if max(s, w0) > cursor:
            gaps.append((cursor, max(s, w0)))
        cursor = max(cursor, min(e, w1))
    if w1 > cursor:
        gaps.append((cursor, w1))
    return runs, gaps


@functools.lru_cache(maxsize=2)
def load(path: str):
    """One profile as {"phases", "runs", "gaps", "window"}; ``runs`` and
    ``gaps`` of the first device plane that ran something, no
    execution skipped. None where the file holds no phase or no device
    plane."""
    from jax.profiler import ProfileData
    from trace_reduce import find_trace
    names = stage_names()
    if names is None:
        return None
    if os.path.isdir(path):
        path = find_trace(path)
    data = ProfileData.from_file(path)
    phases = host_phases(data, names)
    for plane in data.planes:
        found = device_window(plane) \
            if plane.name.startswith("/device:") else None
        if phases and found:
            return skip_first({"phases": phases, "runs": found[0],
                               "gaps": found[1]}, 0)
    return None


def skip_first(spans: dict, skip: int) -> dict:
    """The window without the main program's first ``skip`` executions
    (warm-up), as ``trace_reduce`` cuts it: it starts where the first
    counted execution does, and a gap ends where an operation starts,
    so the gaps are clipped and not computed anew."""
    runs = spans["runs"][skip:]
    w0, w1 = runs[0][0], max(e for _, e in runs)
    gaps = [(max(s, w0), e) for s, e in spans["gaps"] if e > w0]
    return {**spans, "runs": runs, "gaps": gaps, "window": (w0, w1)}


def clock_gap_ms(spans: dict):
    """Median, over the phases that blocked on the device and ended
    inside the window, of the time from the end of the last execution
    of the main program before the phase's end to that end, in
    milliseconds; None where there is no such phase."""
    ends = sorted(e for _, e in spans["runs"])
    w1 = spans["window"][1]
    slack = CLOCK_LIMIT_MS * 1e6
    dist = []
    for p in spans["phases"]:
        i = bisect.bisect_right(ends, p["end"])
        if p["name"] in BLOCKS_ON_DEVICE and i \
                and p["end"] - p["start"] >= BLOCKED_FLOOR_NS \
                and p["end"] <= w1 + slack:
            dist.append(p["end"] - ends[i - 1])
    return statistics.median(dist) / 1e6 if dist else None


def for_run(ctx: dict):
    """What the readers read: the spans a test put into the context,
    else those of the traced run's profile, which ``run.py`` keeps at
    ``<root>/.bench_trace/<workload>`` until the readers have run. None
    where there is nothing to read or the clocks do not agree."""
    spans = ctx.get("host_spans")
    if spans is None:
        cell, trace = ctx.get("cell") or {}, ctx.get("trace")
        trace_dir = os.path.join(cell.get("root", ""), ".bench_trace",
                                 cell.get("name", ""))
        if not trace or not trace.get("module_runs") \
                or not os.path.isdir(trace_dir):
            return None
        try:
            spans = load(trace_dir)
        except (FileNotFoundError, ValueError):
            return None
        if not spans or len(spans["runs"]) < trace["module_runs"]:
            return None
        # the reduction that counted the executions skipped the rest
        spans = skip_first(
            spans, len(spans["runs"]) - trace["module_runs"])
    if not spans:
        return None
    gap = clock_gap_ms(spans)
    return spans if gap is not None and gap < CLOCK_LIMIT_MS else None


# ------------------------------------------------------------- what it offers

def request_means_ms(spans: dict) -> dict:
    """Mean over the profile's requests of each wait before the device
    stage, from the sums that ``serve.execute`` carries: equal to the
    engine's histograms' sum over count."""
    rows, sums = 0, defaultdict(float)
    for p in spans["phases"]:
        st = p["stats"]
        if p["name"] == "serve.execute" and "queue_wait_us" in st:
            rows += st["rows"]
            for w in WAITS:
                sums[w] += st[w + "_us"]
    return {w: sums[w] / rows / 1e3 for w in WAITS} if rows else {}


def stage_means_ms(spans: dict) -> dict:
    """Mean over the profile's requests of all seven stages of a
    request, in their order: the four waits as above; ``decode``,
    ``device`` and ``respond`` as the batch's phase on the profiler's
    clock, a sample a row (``respond`` to the batch's last reply: a
    little over what a request sees)."""
    means = request_means_ms(spans)
    for stage, name in (("decode", "serve.decode"),
                        ("device", "serve.execute"),
                        ("respond", "serve.respond")):
        took = [(p["stats"]["rows"], p["end"] - p["start"])
                for p in spans["phases"] if p["name"] == name
                and "rows" in p["stats"] and not p["stats"].get("retry")]
        if took:
            means[stage] = sum(r * t for r, t in took) \
                / sum(r for r, _ in took) / 1e6
    order = ("queue_wait", "collect_wait", "token_wait", "decode",
             "dispatch_wait", "device", "respond")
    return {k: means[k] for k in order if k in means}


def batches(spans: dict) -> list:
    """The host time of each batch of the profile by phase, in ms:
    {"serve.execute": ..., ...}, a batch a dict, in the order of the
    batch numbers that the phases carry; retries left out."""
    by_batch = defaultdict(lambda: defaultdict(float))
    for p in spans["phases"]:
        st = p["stats"]
        if "batch" in st and not st.get("retry"):
            by_batch[st["batch"]][p["name"]] += (p["end"] - p["start"]) / 1e6
    return [dict(v) for _, v in sorted(by_batch.items())]


def worker_host_ms(spans: dict):
    """Per batch, the worker's own host work: ``serve.execute`` and
    ``serve.respond`` less the blocked read inside the first; over the
    batches whose three phases the profile holds."""
    own = [b["serve.execute"] + b["serve.respond"] - b["tpu_model.readback"]
           for b in batches(spans)
           if {"serve.execute", "serve.respond",
               "tpu_model.readback"} <= set(b)]
    return sum(own) / len(own) if own else None


def gap_table(spans: dict) -> list:
    """Each device gap over the floor as (start_ns, length_ns, {name:
    covered_ns}). A thread's phases nest, so every instant of the gap
    is charged, for each thread, to the innermost phase of that thread
    that covers it (the one that started last and, of two that started
    together, the shorter); ``unnamed`` is what no thread's phase
    covers. Two threads in phases at once are both charged, so a gap's
    names can add up to more than its length."""
    phases = [p for p in spans["phases"]
              if p["name"] not in WAITS_FOR_WORKER]
    table = []
    for g0, g1 in spans["gaps"]:
        if g1 - g0 <= GAP_FLOOR_NS:
            continue
        over = [p for p in phases if p["start"] < g1 and p["end"] > g0]
        cuts = sorted({g0, g1} | {t for p in over
                                  for t in (p["start"], p["end"])
                                  if g0 < t < g1})
        cover = defaultdict(float)
        for a, b in zip(cuts, cuts[1:]):
            inner = {}
            for p in over:      # in order of start, then longest first
                if p["start"] <= a and p["end"] >= b:
                    inner[p["thread"]] = p["name"]
            for name in inner.values() or [UNNAMED]:
                cover[name] += b - a
        table.append((g0, g1 - g0, dict(cover)))
    return table


def idle_named_percent(spans: dict):
    """Share of the device's idle time in gaps over the floor that lies
    under a named host phase."""
    table = gap_table(spans)
    idle = sum(length for _, length, _ in table)
    if not idle:
        return None
    unnamed = sum(cover.get(UNNAMED, 0.0) for _, _, cover in table)
    return 100.0 * (1.0 - unnamed / idle)


def read_idle_named(ctx: dict):
    """The reader of every ``device_idle_*_named`` metric."""
    spans = for_run(ctx)
    return spans and idle_named_percent(spans)


# ------------------------------------------------------------------ printing

def report(spans: dict) -> str:
    w0, w1 = spans["window"]
    out = [f"window {(w1 - w0) / 1e9:.3f} s, {len(spans['runs'])} "
           f"executions of the main program, clock check "
           f"{clock_gap_ms(spans)} ms (limit {CLOCK_LIMIT_MS})"]
    means = stage_means_ms(spans)
    if means:
        out.append("request stage means, ms: " + ", ".join(
            f"{k} {v:.3f}" for k, v in means.items())
            + f"; sum {sum(means.values()):.3f}")
    per = batches(spans)
    if per:
        names = sorted({n for b in per for n in b})
        has = {n: [b[n] for b in per if n in b] for n in names}
        out.append(f"host time a batch over {len(per)} batches, ms: "
                   + ", ".join(f"{n} {sum(v) / len(v):.3f}"
                               for n, v in has.items()))
        out.append(f"worker_host_ms {worker_host_ms(spans)}")
    table = gap_table(spans)
    by_name = defaultdict(lambda: [0, 0.0])
    for _, length, cover in table:
        top = max(cover, key=cover.get)
        by_name[top][0] += 1
        by_name[top][1] += length
    out.append(f"{len(table)} device gaps over {GAP_FLOOR_NS / 1e6} ms, "
               f"{sum(g[1] for g in table) / 1e6:.3f} ms idle, named "
               f"{idle_named_percent(spans)} %")
    out.append("gaps by the phase that covers most: " + ", ".join(
        f"{n} {c} x, {t / 1e6:.3f} ms" for n, (c, t) in sorted(
            by_name.items(), key=lambda kv: -kv[1][1])))
    covered = defaultdict(float)
    for _, _, cover in table:
        for n, t in cover.items():
            covered[n] += t
    out.append("idle time by innermost phase, a thread each, ms: "
               + ", ".join(f"{n} {t / 1e6:.3f}" for n, t in sorted(
                   covered.items(), key=lambda kv: -kv[1])))
    for g0, length, cover in sorted(table, key=lambda g: -g[1])[:10]:
        out.append(f"  gap at {(g0 - w0) / 1e9:.3f} s, {length / 1e6:.3f} "
                   "ms: " + ", ".join(f"{n} {t / 1e6:.3f}" for n, t in
                                      sorted(cover.items(),
                                             key=lambda kv: -kv[1])))
    return "\n".join(out)


def main(argv) -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [p for p in (here, os.path.dirname(here))
                    if p not in sys.path]
    spans = load(argv[1])
    if spans is None:
        print("no host phases, or no device plane, in this profile")
        return 1
    print(report(skip_first(spans, int(argv[2]) if len(argv) > 2 else 0)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
