"""exec_host_ms_per_kq: host time of the executor's stages around the
device calls (``executor.fetch``, ``.resolve``, ``.sort``, ``.knn_seed``,
``.knn_select`` spans) per 1,000 queries (``executor.queries``)."""

STAGES = frozenset({"executor.fetch", "executor.resolve", "executor.sort",
                    "executor.knn_seed", "executor.knn_select"})


def read(ctx):
    q = ctx.counters.get("executor.queries", 0)
    ns = sum(s.dur_ns for s in ctx.spans if s.name in STAGES)
    if not q or not ns:
        return None
    return ns / 1e6 / (q / 1e3)
