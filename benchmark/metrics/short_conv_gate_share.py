"""The gated short convolution's element-wise part as a share of the
device's busy time: the operations under the scope ``short_conv_gate``
(B * z, the three taps, C *: everything between the operator's two
products) over busy time. Near zero where they fused into their
neighbours."""


def read(ctx):
    import xplane_scopes
    t = ctx.get("trace")
    scope_of = xplane_scopes.for_run(ctx)
    if not t or not scope_of or t["busy_s"] <= 0:
        return None
    seconds, n = xplane_scopes.seconds_under(t, scope_of, "short_conv_gate")
    if not n:
        return None
    return 100.0 * seconds / (t["busy_s"] * t.get("planes", 1))
