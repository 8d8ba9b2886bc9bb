"""Mean of ``TPUModel``'s ``device_ms`` over the window: the host's wait
from dispatch to read-back of one micro-batch, which is what the name
says and not the device's own time."""


def read(ctx):
    return ctx["counters"].get("device_wait_ms")
