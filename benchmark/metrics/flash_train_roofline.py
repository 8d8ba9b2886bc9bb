"""The flash attention kernels' share of their roofline in the training
step: the least time the chip could take for the forward and backward
of every layer of every traced step (operations and bytes from shapes,
flops.py) over the time the flash calls took on the device. The trace
has to hold one forward call a layer and step, and a whole number of
backward calls (two today: dq, and dk with dv)."""


def read(ctx):
    from flops import (flash_backward_cost, flash_forward_cost,
                       roofline_seconds)
    from trace_reduce import (FLASH_BACKWARD, FLASH_FORWARD, calls_per,
                              kernel_seconds)
    t, peak = ctx.get("trace"), ctx.get("peak")
    if not t or not peak:
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    c = ctx["counters"]
    layer_steps = t["module_runs"] * c["steps_per_dispatch"] * spec["depth"]
    fwd_s, fwd_n = kernel_seconds(t, FLASH_FORWARD)
    bwd_s, bwd_n = kernel_seconds(t, FLASH_BACKWARD)
    if calls_per(fwd_n, layer_steps) != 1 \
            or calls_per(bwd_n, layer_steps) is None:
        return None
    shape = (c["batch"], spec["heads"], c["seq"],
             spec["dim"] // spec["heads"])
    least = layer_steps * (
        roofline_seconds(flash_forward_cost(*shape), peak)["seconds"]
        + roofline_seconds(flash_backward_cost(*shape), peak)["seconds"])
    return 100.0 * least / (fwd_s + bwd_s)
