"""kernel_ms_per_kq: device time of the jitted query programs in the
traced window (``trace_reduce.QUERY_PROGRAMS``), per 1,000 queries."""


def read(ctx):
    q = ctx.counters.get("executor.queries", 0)
    if ctx.trace is None or not q or not ctx.trace["query_program_s"]:
        return None
    return ctx.trace["query_program_s"] * 1e3 / (q / 1e3)
