"""From a profiler trace (.xplane.pb) to device busy time, idle gaps and
per-operation time. Reads the file with jax's own ProfileData.

A device plane carries an ``XLA Modules`` line (one event per program
execution) and an ``XLA Ops`` line (one event per operation). Control
flow (``while``, ``conditional``, ``call``) spans its children on the
same line and is left out by its opcode, so busy time is the union of
leaf events. (Not by structure: markers of no length, such as a
``ConcatBitcast`` custom call, start with the kernel that follows them,
and a rule of "contains a later event" drops that kernel.) The window runs from the start of the main program's first
counted execution to the end of its last; the main program is the one
with the most device time.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict


# The flash kernels reach the trace as custom calls named after the
# jitted wrappers of ops/flash_attention.py, e.g.
# '%_flash_backward.4 = (bf16[..], bf16[..]) custom-call(...)'
FLASH_FORWARD = r"^%?[\w.\-]*flash_forward[\w.\-]* = .*custom-call\("
FLASH_BACKWARD = r"^%?[\w.\-]*flash_backward[\w.\-]* = .*custom-call\("


def find_trace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def short_name(name: str) -> str:
    """'%fusion.12 = bf16[..] fusion(...)' -> 'fusion.12'."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:96]


_CONTROL_FLOW = re.compile(r" (while|conditional|call)\(")


def _leaves(events):
    """Events (start, end, name) that are not control flow around
    others; ``name`` is the instruction's text, opcode included."""
    return sorted(e for e in events if not _CONTROL_FLOW.search(e[2]))


def reduce_plane(plane, skip_first: int = 0) -> dict | None:
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
    runs = sorted(mods[main])[skip_first:]
    if not runs:
        return None
    w0, w1 = runs[0][0], max(e for _, e in runs)
    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
           for ev in lines["XLA Ops"].events
           if ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1]
    leaves = _leaves(ops)
    busy = 0.0
    by_name = defaultdict(lambda: [0.0, 0])
    gaps = []
    cursor = w0
    for s, e, name in leaves:
        s_c, e_c = max(s, w0), min(e, w1)
        if s_c > cursor:
            gaps.append((cursor, s_c))
        if e_c > cursor:
            busy += e_c - max(s_c, cursor)
            cursor = e_c
        by_name[name][0] += e - s
        by_name[name][1] += 1
    if w1 > cursor:
        gaps.append((cursor, w1))
    return {"plane": plane.name, "main_module": main,
            "module_runs": len(runs),
            "window_s": (w1 - w0) / 1e9, "busy_s": busy / 1e9,
            "ops": {n: {"seconds": t / 1e9, "count": c}
                    for n, (t, c) in by_name.items()},
            "gaps": [((s - w0) / 1e9, (e - s) / 1e9) for s, e in gaps]}


def reduce_trace(path: str, skip_first: int = 0) -> dict:
    """Busy and window averaged over the device planes that ran
    something; operations summed over them."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_trace(path)
    data = ProfileData.from_file(path)
    planes = [r for r in (reduce_plane(p, skip_first) for p in data.planes
                          if p.name.startswith("/device:"))
              if r is not None and r["busy_s"] > 0]
    if not planes:
        raise ValueError(f"no device plane with operations in {path}")
    ops = defaultdict(lambda: {"seconds": 0.0, "count": 0})
    for r in planes:
        for n, v in r["ops"].items():
            ops[n]["seconds"] += v["seconds"]
            ops[n]["count"] += v["count"]
    n = len(planes)
    return {"planes": n, "main_module": planes[0]["main_module"],
            "module_runs": planes[0]["module_runs"],
            "window_s": sum(r["window_s"] for r in planes) / n,
            "busy_s": sum(r["busy_s"] for r in planes) / n,
            "ops": dict(ops), "gaps": planes[0]["gaps"]}


def kernel_seconds(reduced: dict, pattern: str) -> tuple:
    """Time and count of the operations whose full name matches."""
    rx = re.compile(pattern)
    hit = [v for n, v in reduced["ops"].items() if rx.search(n)]
    return (sum(v["seconds"] for v in hit), sum(v["count"] for v in hit))


def calls_per(count: int, units: int):
    """How many calls of a kernel each unit of work (a layer of a step)
    made, or None where the trace does not hold a whole number of
    them: then something other than the expected kernel was matched,
    or part of the work ran out of the reader's sight."""
    if units <= 0 or count <= 0 or count % units:
        return None
    return count // units


def idle_percent(ctx: dict):
    """Share of the traced window in which no operation ran on the
    device: the reader of every ``device_idle_*`` metric."""
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1]["seconds"])
    gaps = sorted(reduced["gaps"], key=lambda g: -g[1])
    return {"device_ops": [[short_name(n), v["seconds"]]
                           for n, v in ops[:top]],
            "idle_gaps": [[f"gap_at_{at:.3f}s", dur]
                          for at, dur in gaps[:top]]}
