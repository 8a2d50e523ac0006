"""queue_wait_p99_ms: 99th percentile (nearest rank) of the
``serving.wait`` spans in the window: from a request's admission to the
pop of the batch that serves it."""


def read(ctx):
    waits = sorted(s.dur_ns / 1e6 for s in ctx.spans
                   if s.name == "serving.wait")
    return ctx.nearest_rank(waits, 99)
