"""What the shared expert costs, as a share of the device's busy time:
the operations under the scope ``moe_shared`` (a gated feed-forward of
every token, run as plain products beside the routed experts' grouped
kernels) over busy time. It is 4.7% of the ``trinity-mini-stage``
step's needed operations. Silent where the program names no
``moe_shared`` scope (no cell reads it for ``glm-5.2-ep16``, which has
one)."""


def read(ctx):
    from trace_trinity import scope_share
    return scope_share(ctx, "moe_shared")
