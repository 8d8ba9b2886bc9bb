"""The worker's own host work a batch: ``serve.execute`` and
``serve.respond`` less ``tpu_model.readback``, the blocked read. It is
what the device's gap between two batches waits for: reply, pad,
``device_put``, dispatch."""


def read(ctx):
    import host_spans
    spans = host_spans.for_run(ctx)
    return spans and host_spans.worker_host_ms(spans)
