"""The ``granite-4.0-h-micro`` step's share of the bf16 peak while it
runs: the operations that the rows the window answered need
(``flops_granite``: the Mamba-2 projections, the conv, the scan's
chunked work counted as the lower triangles it is, the attention
projections and causal pairs, the dense feed-forward of every layer,
the head at the last position; padded rows not counted) over the
device's busy time in the trace. Silent where the program's counter is
missing."""


def read(ctx):
    from flops_granite import forward_flops_per_row
    t, peak, c = ctx.get("trace"), ctx.get("peak"), ctx["counters"]
    if not t or not peak or not c.get("rows_ok") or t["busy_s"] <= 0 \
            or not c.get("ssm_layers"):
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    need = forward_flops_per_row(spec, c["seq"]) * c["rows_ok"]
    return 100.0 * need / (t["busy_s"] * peak["bf16_flops"]
                           * ctx["cell"]["chips"])
