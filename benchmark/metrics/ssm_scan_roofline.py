"""The selective scan's share of its roofline in the served step: the
least time the chip could take for the scan's needed work of every
Mamba-2 layer of every traced execution (``flops_granite.scan_cost``,
from the shapes whatever implements it: the chunked algorithm's
operations at the published chunk, x, dt, B and C read and y written
once; a bucket at the window's mean of real rows), over the device time
of the operations under the scope ``ssm_scan`` (dt's softplus, the
decays, the chunked scan and the D skip). Silent where the program
names no such scope or counts no Mamba-2 layer."""


def read(ctx):
    import xplane_scopes
    from flops import roofline_seconds
    from flops_granite import scan_cost
    t, peak, c = ctx.get("trace"), ctx.get("peak"), ctx["counters"]
    scope_of = xplane_scopes.for_run(ctx)
    if not t or not peak or not scope_of or not c.get("ssm_layers") \
            or not c.get("batch_rows"):
        return None
    seconds, n = xplane_scopes.seconds_under(t, scope_of, "ssm_scan")
    if not n or seconds <= 0:
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    least = roofline_seconds(scan_cost(spec, c["batch_rows"], c["seq"]),
                             peak)["seconds"]
    return 100.0 * least * c["ssm_layers"] * t["module_runs"] * t.get(
        "planes", 1) / seconds
