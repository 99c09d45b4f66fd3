"""The flash attention kernel's share of its roofline in the delta-rule
tower's one latent attention layer, whose keys (192, nothing rotated)
are wider than its values (128): the least time the chip could take for
causal attention proper in a step (``costs_hyper_seq.
flash_least_seconds``, which counts the two widths apart: the forward
pass once and the backward pass once, seven products of which four
contract or emit 192 and three 128, heads x 1152 x T (T + 1) / 2
multiply-accumulates, the larger of operations over the bf16 peak and
bytes over the HBM peak; a recomputed or split call adds device time and
no need), over the device time a step of the trace group
``flash_attention``: the name the compiler gives the Pallas calls after
the innermost scope around them. A step that runs no such call has
nothing to read here."""

import costs_kda_seq
from weights_kda_seq import sizes_of

GROUP = "flash_attention"


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    seconds = sum(s for group, s in r.trace["ops"] if group == GROUP)
    if seconds <= 0:
        return None
    length = min(r.env.mix["session_length"], r.batch)
    least = costs_kda_seq.flash_least_seconds(
        sizes_of(r.config), length, r.batch // length, r.peaks)
    return 100.0 * least * r.trace["steps"] / seconds
