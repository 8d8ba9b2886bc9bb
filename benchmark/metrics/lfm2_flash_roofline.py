"""The flash forward kernel's share of its roofline under grouped-query
attention: the least time the chip could take for one causal call an
attention layer of every traced bucket, each at the bucket's rows (the
call's own shape; K and V read once a key/value head), over the time
the ``_flash_forward`` calls took on the device. Silent unless the
trace holds one call an attention layer and execution."""


def read(ctx):
    from flops import roofline_seconds
    from flops_lfm2 import flash_cost
    from trace_reduce import FLASH_FORWARD, calls_per, kernel_seconds
    t, peak = ctx.get("trace"), ctx.get("peak")
    if not t or not peak:
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    c = ctx["counters"]
    layers = t["module_runs"] * sum(
        1 for kind in spec["layer_types"] if kind == "full_attention")
    seconds, count = kernel_seconds(t, FLASH_FORWARD)
    if calls_per(count, layers) != 1 or not c.get("bucket"):
        return None
    least = roofline_seconds(flash_cost(spec, c["bucket"], c["seq"]),
                             peak)["seconds"]
    return 100.0 * layers * least / seconds
