"""Mean host time per step in the program's ``cache/miss_import`` span
(victim buffer, then the miss rows with their state from the PS over RPC)
over the traced seconds: the miss-import part of a cached cell's
``train_call_ms``."""

import program_spans


def read(r):
    return program_spans.mean_ms("cache/miss_import")
