"""Mean host time per step in the program's ``trainer/dispatch`` span (the
call of the jitted cached step, the upload of its arguments included)
over the traced seconds: the dispatch part of a cached cell's
``train_call_ms``."""

import program_spans


def read(r):
    return program_spans.mean_ms("trainer/dispatch")
