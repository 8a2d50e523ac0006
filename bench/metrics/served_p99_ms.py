"""served_p99_ms: 99th percentile latency of every request due in the
window; shed and failed requests sort above every completed one.  One
request that holds the serving front's single drain loop for most of a
second sets it, so it swings with where that request lands in the
arrival order and with the rows: read per layer, beside the end-to-end
median."""


def read(ctx):
    return ctx.nearest_rank(ctx.latencies_ms, 99)
