"""The flash kernels' share of their roofline in the sliding-window
tower's two ``A`` layers: the least time the chip could take for a
step's causal attention there (``costs_window_seq.flash_least_seconds``:
the forward pass once and the backward pass once, seven products of 32 x
128 x T(T + 1)/2 multiply-accumulates a layer, 9.77 ms a layer at 8192
events), over the device time a step of the trace group
``flash_attention``: the name the compiler gives the Pallas calls after
the innermost scope around them, which the ``A`` layers put there and the
``W`` layers do not. A step that runs no such call has nothing to read
here."""

import costs_window_seq
from weights_window_seq import sizes_of

GROUP = "flash_attention"


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    seconds = sum(s for group, s in r.trace["ops"] if group == GROUP)
    if seconds <= 0:
        return None
    length = min(r.env.mix["session_length"], r.batch)
    least = costs_window_seq.flash_least_seconds(
        sizes_of(r.config), length, r.batch // length, r.peaks)
    return 100.0 * least * r.trace["steps"] / seconds
