"""What the sliding-window layers' attention costs, as a share of the
device's busy time: the operations under the scope ``swa_attend`` (the
banded flash call and the layout copies around it) over busy time. The
banded pairs are 6.5% of the step's needed operations; with every key
block visited the same layers' calls would cost what the full layers'
do. Silent where the program names no ``swa_attend`` scope."""


def read(ctx):
    import xplane_scopes
    t = ctx.get("trace")
    scope_of = xplane_scopes.for_run(ctx)
    if not t or not scope_of or t["busy_s"] <= 0:
        return None
    seconds, n = xplane_scopes.seconds_under(t, scope_of, "swa_attend")
    if not n:
        return None
    return 100.0 * seconds / (t["busy_s"] * t.get("planes", 1))
