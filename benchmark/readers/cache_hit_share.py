"""Share of the window's cache lookups that hit (``/stats -> cache``)."""

from benchmark.readers._stats import delta


def read(ctx):
    hits, misses = delta(ctx, "cache.hits_total"), delta(ctx, "cache.misses_total")
    coalesced = delta(ctx, "cache.coalesced_total") or 0
    if hits is None or misses is None or hits + misses + coalesced == 0:
        return None
    return 100.0 * (hits + coalesced) / (hits + misses + coalesced)
