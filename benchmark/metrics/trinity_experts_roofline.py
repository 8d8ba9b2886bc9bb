"""The grouped products' share of their roofline in the served step:
the least time the chip could take for gate, up and down of every
routed expert over the (token, expert) pairs that the program counted in
the window (``flops_trinity.experts_cost``, a layer of the *expert*
layers, the leading dense ones not among them, and bucket at the
window's mean of real rows), over the device time of the Pallas calls
under the scope ``moe_experts``. The shared expert runs as plain
products under ``moe_shared`` and is ``moe_shared_share``'s. Padded
rows pass through the calls and are no needed work."""

GROUPED = r"custom-call\("


def read(ctx):
    import re
    import xplane_scopes
    from flops import roofline_seconds
    from flops_trinity import expert_layers, experts_cost
    t, peak, c = ctx.get("trace"), ctx.get("peak"), ctx["counters"]
    scope_of = xplane_scopes.for_run(ctx)
    if not t or not peak or not scope_of or not c.get("moe_tokens_held") \
            or not c.get("batch_rows"):
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    layers = expert_layers(spec)
    rx = re.compile(GROUPED)
    seconds = sum(v["seconds"] for n, v in t["ops"].items()
                  if rx.search(n)
                  and "moe_experts" in scope_of.get(n, "").split("/"))
    if layers <= 0 or seconds <= 0:
        return None
    # pairs a layer and bucket: the per-row count is summed over layers
    pairs = c["moe_tokens_held"] / layers * c["batch_rows"]
    least = roofline_seconds(experts_cost(spec, pairs), peak)["seconds"]
    return 100.0 * least * layers * t["module_runs"] / seconds
