"""What attention's output gate costs, as a share of the device's busy
time: the operations under the scope ``attn_gate`` (the fifth
projection W_g u, its sigmoid and the product with the heads' outputs
before W_o) over busy time. The projection is 7.9% of the
``trinity-mini-stage`` step's needed operations. Silent where the
program names no ``attn_gate`` scope."""


def read(ctx):
    from trace_trinity import scope_share
    return scope_share(ctx, "attn_gate")
