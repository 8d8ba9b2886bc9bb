"""The selected-set attention kernel's share of its roofline in the
served step: the least time the chip could take for the selected pairs
of one sequence (``flops_glm_dsa.attend_cost``), times the calls the
trace holds (one a layer and row of every traced bucket, padded rows
too: the call's own shape), over the device time of the custom calls
named ``dsa_attend``."""

DSA_ATTEND = r"^%?[\w.\-]*dsa_attend[\w.\-]* = .*custom-call\("


def read(ctx):
    from flops import roofline_seconds
    from flops_glm_dsa import attend_cost
    from trace_reduce import calls_per, kernel_seconds
    t, peak = ctx.get("trace"), ctx.get("peak")
    if not t or not peak:
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    c = ctx["counters"]
    layers = t["module_runs"] * len(spec["indexer_types"])
    seconds, count = kernel_seconds(t, DSA_ATTEND)
    if calls_per(count, layers) != c.get("bucket"):
        return None
    least = roofline_seconds(attend_cost(spec, c["seq"]), peak)["seconds"]
    return 100.0 * count * least / seconds
