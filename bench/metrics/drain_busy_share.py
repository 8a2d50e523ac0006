"""drain_busy_share: the union of the ``serving.batch`` spans (the drain
thread serving a batch) over the traced window, in percent."""


def read(ctx):
    ivs = sorted((s.t0_ns, s.t1_ns) for s in ctx.spans
                 if s.name == "serving.batch")
    if not ivs:
        return None
    window_s = (ctx.trace or {}).get("window_s") or ctx.seconds
    busy, end = 0, None
    for a, b in ivs:
        if end is None or a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return 100.0 * busy / 1e9 / window_s
