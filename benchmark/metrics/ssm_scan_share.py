"""What the selective scan costs, as a share of the device's busy time:
the operations under the scope ``ssm_scan`` (dt's softplus, the decays,
the chunked scan and the D skip of every Mamba-2 layer) over busy time.
The scan is 1.9% of the ``granite-4.0-h-micro`` step's needed
operations. Silent where the program names no ``ssm_scan`` scope."""


def read(ctx):
    from trace_trinity import scope_share
    return scope_share(ctx, "ssm_scan")
