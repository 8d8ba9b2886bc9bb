"""What the routed experts spend around their products, as a share of
the device's busy time: the operations under the scope ``moe_experts``
that are not under ``moe_grouped`` inside it (the sort of the pairs,
the gather of their inputs, the gates' scaling and the scatter-add of
the outputs) over busy time. Silent where the program names no
``moe_grouped`` scope."""


def read(ctx):
    import xplane_scopes
    t = ctx.get("trace")
    scope_of = xplane_scopes.for_run(ctx)
    if not t or not scope_of or t["busy_s"] <= 0:
        return None
    whole, _ = xplane_scopes.seconds_under(t, scope_of, "moe_experts")
    products, n = xplane_scopes.seconds_under(t, scope_of, "moe_grouped")
    if not n:
        return None
    return 100.0 * (whole - products) / (t["busy_s"] * t.get("planes", 1))
