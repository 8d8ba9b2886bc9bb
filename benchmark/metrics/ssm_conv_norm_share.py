"""What the Mamba-2 mixer's element-wise passes cost, as a share of the
device's busy time: the operations under the scopes ``ssm_conv`` (the
causal conv over 4352 channels, its bias and the silu) and
``ssm_gated_norm`` (y * silu(z) and its norm over 4096) over busy time.
Silent unless the program names both scopes."""


def read(ctx):
    from trace_trinity import scope_share
    parts = [scope_share(ctx, scope)
             for scope in ("ssm_conv", "ssm_gated_norm")]
    if None in parts:
        return None
    return sum(parts)
