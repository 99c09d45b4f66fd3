"""Mean host time per step in the program's ``trainer/place_batch`` span
(``shard_batch_pytree``: the host-to-device copies of one batch) over the
traced seconds: the placement part of ``train_call_ms``."""

import program_spans


def read(r):
    return program_spans.mean_ms("trainer/place_batch")
