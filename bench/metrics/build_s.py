"""build_s: host clock around the out-of-core ``build_segment``."""


def read(ctx):
    return ctx.timings.get("build_s")
