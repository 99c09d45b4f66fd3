"""Mean host time per step in the program's ``cache/map`` span (the
sign-to-slot mapper and its counters) over the traced seconds: the mapper
part of a cached cell's ``train_call_ms``."""

import program_spans


def read(r):
    return program_spans.mean_ms("cache/map")
