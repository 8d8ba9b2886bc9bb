"""The whole training step's share of the chip's bf16 peak: tokens per
second of the window times the operations a token needs (flops.py) over
the peak times the chips used."""


def read(ctx):
    from flops import train_flops_per_token
    c = ctx["counters"]
    if not ctx.get("peak") or "tokens_per_s" not in c:
        return None
    spec = ctx["cell"]["config_file"]["networkSpec"]
    need = train_flops_per_token(spec, c["seq"]) * c["tokens_per_s"]
    return 100.0 * need / (ctx["peak"]["bf16_flops"]
                           * ctx["cell"]["chips"])
