"""The flash kernels' share of their roofline in the sliding-window
tower's six ``W`` layers: the least time the chip could take for a
step's attention over the band (``costs_window_seq.window_least_seconds``:
the forward pass once and the backward pass once, seven products of 32 x
128 multiply-accumulates a pair of the band, 7 864 832 pairs a layer at
8192 events and a window of 1024), over the device time a step of the
trace group ``window_attention``: the name the compiler gives the Pallas
calls after the innermost scope around them, which the ``W`` layers put
there. The same need whatever implements it: a kernel that walked every
causal pair under a mask could read at most 0.234 of what a walk of the
band reads. A step that runs no such call (a program without the band
schedule) has nothing to read here."""

import costs_window_seq
from weights_window_seq import sizes_of

GROUP = "window_attention"


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    seconds = sum(s for group, s in r.trace["ops"] if group == GROUP)
    if seconds <= 0:
        return None
    length = min(r.env.mix["session_length"], r.batch)
    least = costs_window_seq.window_least_seconds(
        sizes_of(r.config), length, r.batch // length, r.peaks)
    return 100.0 * least * r.trace["steps"] / seconds
