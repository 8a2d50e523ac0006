"""setup_s: process start to the first timed request (host clock):
rows, curve fit, index or segment build, upload, warm-up."""


def read(ctx):
    return ctx.setup_s
