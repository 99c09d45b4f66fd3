"""The delta rule's kernels' share of their roofline in the delta-rule
tower's four ``K`` layers: the least time the chip could take for the
recurrence of a step whatever form an implementation gives it
(``costs_kda_seq.kda_least_seconds``: the position-by-position form's
operations, forward once and backward twice that, over the bf16 peak, or
the bytes of ``q``, ``k``, ``v``, ``o``, ``g``, ``beta`` and their
gradients once over the HBM peak, whichever is larger; the chunked
form's further products, a recomputed chunk and the kept states add
device time and no need), over the device time a step of the trace group
``kda_scan``: the name the compiler gives the Pallas calls after the
innermost scope around them. The glue between the calls is not in the
group. A step whose recurrence is no kernel of its own has no such group
and nothing to read here."""

import costs_kda_seq
from weights_kda_seq import sizes_of

GROUP = "kda_scan"


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    seconds = sum(s for group, s in r.trace["ops"] if group == GROUP)
    if seconds <= 0:
        return None
    length = min(r.env.mix["session_length"], r.batch)
    least = costs_kda_seq.kda_least_seconds(
        sizes_of(r.config), length, r.batch // length, r.peaks)
    return 100.0 * least * r.trace["steps"] / seconds
