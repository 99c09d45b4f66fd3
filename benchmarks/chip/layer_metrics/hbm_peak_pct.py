"""Peak bytes in use on the fullest device after the window, over that
device's limit (``memory_stats()``)."""


def read(r):
    if r.memory_peak is None:
        return None
    return 100.0 * r.memory_peak / r.memory_limit
