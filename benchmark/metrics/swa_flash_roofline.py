"""The flash forward kernel's share of its roofline on the sliding-
window layers: the least time the chip could take for one banded
grouped-query call a sliding layer of every traced bucket, each at the
bucket's rows (``flops_mellum2.flash_cost``: the pairs with
0 <= p - j < sliding_window; q read once, K and V once a key/value
head), over the device time of the ``_flash_forward`` calls under the
scope ``swa_attend``. Silent unless they are one a sliding layer and
execution."""


def read(ctx):
    from trace_mellum2 import flash_roofline
    return flash_roofline(ctx, "sliding_attention", "swa_attend")
