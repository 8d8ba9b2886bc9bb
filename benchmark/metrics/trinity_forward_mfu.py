"""The ``trinity-mini-stage`` step's share of the bf16 peak while it
runs: the operations that the rows the window answered need
(``flops_trinity``: the five projections, the banded pairs of the
sliding layers and the causal pairs of the full one, the dense layer,
the shared expert, the (token, expert) pairs as the program counted
them, padded rows not counted) over the device's busy time in the trace.
Silent where the program's counter is missing."""


def read(ctx):
    from flops_trinity import forward_flops_per_row
    t, peak, c = ctx.get("trace"), ctx.get("peak"), ctx["counters"]
    if not t or not peak or not c.get("rows_ok") or t["busy_s"] <= 0 \
            or not c.get("moe_tokens_held"):
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    need = forward_flops_per_row(spec, c["seq"], c["moe_tokens_held"]) \
        * c["rows_ok"]
    return 100.0 * need / (t["busy_s"] * peak["bf16_flops"]
                           * ctx["cell"]["chips"])
