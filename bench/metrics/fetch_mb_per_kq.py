"""fetch_mb_per_kq: bytes the query programs' results bring to the host
(``executor.fetch_bytes``), in MB per 1,000 queries
(``executor.queries``)."""


def read(ctx):
    q = ctx.counters.get("executor.queries", 0)
    b = ctx.counters.get("executor.fetch_bytes", 0)
    if not q or not b:
        return None
    return b / 1e6 / (q / 1e3)
