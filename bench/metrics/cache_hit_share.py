"""cache_hit_share: page-group cache hits over lookups in the window
(``store.cache.hits`` / (hits + misses)), in percent."""


def read(ctx):
    h = ctx.counters.get("store.cache.hits", 0)
    n = h + ctx.counters.get("store.cache.misses", 0)
    return 100.0 * h / n if n else None
