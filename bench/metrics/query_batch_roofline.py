"""query_batch_roofline: the least time the window's work takes at the
chip's HBM bandwidth, over the query programs' device time, in percent.

Work: for every Count, Range and Point window sent, the rows on the
pages whose MBR intersects it, at d x 4 bytes a row (``run.
window_work_bytes``).  kNN's box retrieval runs in the same programs
but its box is chosen inside the program, so its work is left out: the
share is a lower bound."""


def read(ctx):
    if ctx.trace is None or not ctx.work_bytes or ctx.peaks is None:
        return None
    t = ctx.trace["query_program_s"]
    if not t:
        return None
    return 100.0 * ctx.work_bytes / ctx.peaks["hbm_bytes_per_s"] / t
