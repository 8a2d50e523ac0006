"""idle_share: 1 - the union of device-busy intervals over the traced
window, in percent."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
