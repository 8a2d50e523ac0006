"""fit_s: host clock around ``Database.fit`` (SMBO curve learning, and
for in-memory configurations the index build)."""


def read(ctx):
    return ctx.timings.get("fit_s")
