"""The flash forward kernel's share of its roofline in the served step:
the least time the chip could take for one call a layer of every traced
batch, each at the bucket's rows (the call's own shape), over the time
the flash forward calls took on the device."""


def read(ctx):
    from flops import flash_forward_cost, roofline_seconds
    from trace_reduce import FLASH_FORWARD, calls_per, kernel_seconds
    t, peak = ctx.get("trace"), ctx.get("peak")
    if not t or not peak:
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    c = ctx["counters"]
    layers = t["module_runs"] * spec["depth"]
    seconds, count = kernel_seconds(t, FLASH_FORWARD)
    if calls_per(count, layers) != 1:
        return None
    cost = flash_forward_cost(c["bucket"], spec["heads"], c["seq"],
                              spec["dim"] // spec["heads"])
    return 100.0 * layers * roofline_seconds(cost, peak)["seconds"] \
        / seconds
