"""Mean host time per step in the program's ``trainer/dispatch`` span (a
device-mode step's call of its jitted program, ``DeviceStep.__call__``:
argument handling, dispatch, the result buffers made) over the traced
seconds: the dispatch part of a device cell's ``train_call_ms``, beside
``place_batch_ms``."""

import program_spans


def read(r):
    return program_spans.mean_ms("trainer/dispatch")
