"""Mean over the window's requests of the ``dispatch_wait`` stage: the
batch lying in the dispatch queue until the worker takes it. Read from
the sums that the ``serve.execute`` phases carry in the profile."""


def read(ctx):
    import host_spans
    spans = host_spans.for_run(ctx)
    return spans and host_spans.request_means_ms(spans).get("dispatch_wait")
