"""assemble_ms_per_batch: time in ``store.assemble`` spans over the
window, per drained serving batch."""


def read(ctx):
    ns = sum(s.dur_ns for s in ctx.spans if s.name == "store.assemble")
    b = ctx.stats["batches"]
    if not ns or not b:
        return None
    return ns / 1e6 / b
