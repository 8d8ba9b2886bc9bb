"""The selector's share of the device's busy time in the served step:
the operations under the scopes ``dsa_score`` (index scores) and
``dsa_topk`` (the exact top-k) over busy time. The scopes are read from
the operations' metadata in the profile (``xplane_scopes``)."""


def read(ctx):
    import xplane_scopes
    t = ctx.get("trace")
    scope_of = xplane_scopes.for_run(ctx)
    if not t or not scope_of or t["busy_s"] <= 0:
        return None
    score, n_score = xplane_scopes.seconds_under(t, scope_of, "dsa_score")
    topk, n_topk = xplane_scopes.seconds_under(t, scope_of, "dsa_topk")
    if not n_score or not n_topk:
        return None
    return 100.0 * (score + topk) / (t["busy_s"] * t.get("planes", 1))
