"""Share of probed signs served from the HBM cache over the window: the
delta of the program's ``device_cache_hits_total`` over hits + misses."""


def read(r):
    hits = r.counters.get("device_cache_hits_total")
    misses = r.counters.get("device_cache_misses_total")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
