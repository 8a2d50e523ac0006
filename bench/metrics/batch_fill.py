"""batch_fill: submissions served per drained batch over the window
(the serving front's ``stats()``)."""


def read(ctx):
    b = ctx.stats["batches"]
    return ctx.stats["served"] / b if b else None
