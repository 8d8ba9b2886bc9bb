"""What the two share readers of the ``trinity_score_16k_steady`` cell
share: the device time under one ``jax.named_scope`` of the program as
a share of the device's busy time."""

from __future__ import annotations


def scope_share(ctx, scope: str):
    """100 x the device time of the operations whose scope has
    ``scope`` among its steps, over busy time; None where the run was
    not traced or the program names no such scope."""
    import xplane_scopes
    t = ctx.get("trace")
    scope_of = xplane_scopes.for_run(ctx)
    if not t or not scope_of or t["busy_s"] <= 0:
        return None
    seconds, n = xplane_scopes.seconds_under(t, scope_of, scope)
    if not n:
        return None
    return 100.0 * seconds / (t["busy_s"] * t.get("planes", 1))
