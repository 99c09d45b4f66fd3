"""The flash attention kernel's share of its roofline in the
selected-attention tower's six ``S`` layers: the least time the chip
could take for a step's attention proper over the pairs the indexer
selected (``costs_sparse_seq.selected_least_seconds``: the forward pass
once and the backward pass once, seven products of 32 x 128
multiply-accumulates a selected pair, at the pairs the placement counted
in each layer, its counters ``selected_pairs_layer_<i>`` over
``selected_batches``: a probe of the last batches of the window), over
the device time a step of the trace group ``flash_attention``: the name
the compiler gives the Pallas calls after the innermost scope around
them. The same need whatever implements it: a kernel that walks every
causal pair under a mask can read at most the selected share (0.44 at
8192 events and 2048 keys a query) of what the dense kernel reads, and
the number says how far the call is from what the selection allows. A
step that runs no such call, or a placement that counts no selected
pairs, has nothing to read here."""

import costs_sparse_seq
from weights_sparse_seq import sizes_of

GROUP = "flash_attention"


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    seconds = sum(s for group, s in r.trace["ops"] if group == GROUP)
    probed = r.counters.get("selected_batches")
    if seconds <= 0 or not probed:
        return None
    sz = sizes_of(r.config)
    pairs = [r.counters[f"selected_pairs_layer_{i}"] / probed
             for i in range(sz["pattern"].count("S"))]
    length = min(r.env.mix["session_length"], r.batch)
    least = costs_sparse_seq.selected_least_seconds(sz, length, pairs,
                                                    r.peaks)
    return 100.0 * least * r.trace["steps"] / seconds
