"""The flash forward kernel's share of its roofline on the full
(causal) layers under grouped-query attention with heads of 128: the
least time the chip could take for one causal call a full layer of
every traced bucket (K and V read once a key/value head), over the
device time of the ``_flash_forward`` calls under the scope
``gqa_attend`` (the sliding layers' calls lie under ``swa_attend`` and
are ``swa_flash_roofline``'s). Silent unless they are one a full layer
and execution."""


def read(ctx):
    from trace_mellum2 import flash_roofline
    return flash_roofline(ctx, "full_attention", "gqa_attend")
