"""p50_ms: median latency of every request due in the window, from its
scheduled send."""


def read(ctx):
    return ctx.nearest_rank(ctx.latencies_ms, 50)
