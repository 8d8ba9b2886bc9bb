"""Mean over the window's requests of the ``token_wait`` stage: from the
batch being sealed (or the request's own dequeue, for one that a top-up
took in later) to the batcher holding an in-flight token. Read from the
sums that the ``serve.execute`` phases carry in the profile."""


def read(ctx):
    import host_spans
    spans = host_spans.for_run(ctx)
    return spans and host_spans.request_means_ms(spans).get("token_wait")
