"""Mean wait of a request in the engine's queue over the window, from
the engine's ``queue_wait_ms`` histogram (sum over count: its buckets
are too coarse for a median)."""


def read(ctx):
    return ctx["counters"].get("queue_wait_ms")
