"""escalations_per_kq: escalation rounds (``executor.escalations``) per
1,000 queries (``executor.queries``) in the window."""


def read(ctx):
    q = ctx.counters.get("executor.queries", 0)
    if not q:
        return None
    return ctx.counters.get("executor.escalations", 0) * 1000.0 / q
