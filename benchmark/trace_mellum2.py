"""What the two flash readers of the ``mellum2_score_16k_steady`` cell
share: the ``_flash_forward`` calls of one attention kind's layers,
found by the scope the program puts around them, against the least time
for that kind's pairs."""

from __future__ import annotations

import re


def flash_roofline(ctx, kind: str, scope: str):
    """100 x the least time for one forward call a ``kind`` layer of
    every traced bucket, each at the bucket's rows
    (``flops_mellum2.flash_cost``), over the device time of the
    ``_flash_forward`` calls under ``scope``; None unless they are one
    a layer of that kind and execution."""
    import xplane_scopes
    from flops import roofline_seconds
    from flops_mellum2 import flash_cost, layers_of
    from trace_reduce import FLASH_FORWARD, calls_per
    t, peak, c = ctx.get("trace"), ctx.get("peak"), ctx["counters"]
    scope_of = xplane_scopes.for_run(ctx)
    if not t or not peak or not scope_of or not c.get("bucket"):
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    rx = re.compile(FLASH_FORWARD)
    calls = [v for n, v in t["ops"].items() if rx.search(n)
             and scope in scope_of.get(n, "").split("/")]
    seconds = sum(v["seconds"] for v in calls)
    layers = t["module_runs"] * layers_of(spec, kind)
    if calls_per(sum(v["count"] for v in calls), layers) != 1 \
            or seconds <= 0:
        return None
    least = roofline_seconds(
        flash_cost(spec, kind, c["bucket"], c["seq"]), peak)["seconds"]
    return 100.0 * layers * least / seconds
