"""The index scores' kernels' share of their roofline in the
selected-attention tower's six ``S`` layers: the least time the chip
could take for a step's index scores whatever form an implementation
gives them (``costs_sparse_seq.index_least_seconds``: ``I`` forward once
and the two products that carry its gradient back to the indexer's
queries and to its key, each 16 x 64 multiply-accumulates a causal pair,
over the bf16 peak, or the bytes of the queries, the weights and their
gradients once over the HBM peak, whichever is larger), over the device
time a step of the trace groups ``index_scores`` and
``index_scores:<kind>``: the name the compiler gives the Pallas calls,
forward and pullback, after the innermost scope around them, and the
same with a fusion's kind where it wraps a call in a fusion named after
it (the pullback's, whose output it writes straight into the loop's
stacked result). The need counts the forward once; a
step that runs it once to select and once again in the alignment loss's
pass, or walks key blocks past a query's position, adds device time and
no need. The softmax and the loss between the two calls are not in the
group. A step whose index scores are no kernel of their own has no such
group and nothing to read here."""

import costs_sparse_seq
from weights_sparse_seq import sizes_of

GROUP = "index_scores"


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    seconds = sum(s for group, s in r.trace["ops"]
                  if group.split(":")[0] == GROUP)
    if seconds <= 0:
        return None
    length = min(r.env.mix["session_length"], r.batch)
    least = costs_sparse_seq.index_least_seconds(
        sizes_of(r.config), length, r.batch // length, r.peaks)
    return 100.0 * least * r.trace["steps"] / seconds
