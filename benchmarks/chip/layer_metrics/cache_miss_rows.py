"""Rows per step that the host tiers (worker, RPC, parameter servers) had
to serve: the delta of ``device_cache_misses_total`` over the window's
steps."""


def read(r):
    misses = r.counters.get("device_cache_misses_total")
    if misses is None or not r.steps:
        return None
    return misses / r.steps
