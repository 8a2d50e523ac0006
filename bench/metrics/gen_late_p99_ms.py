"""gen_late_p99_ms: 99th percentile of how late the open-loop generator
sent a request against its schedule (a starved generator would read as
a fast server)."""


def read(ctx):
    late = sorted(s.late_s * 1e3 for s in ctx.sent)
    return ctx.nearest_rank(late, 99)
