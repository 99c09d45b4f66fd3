"""The tower's Pallas kernels' share of their roofline, all of them in one
number: the least time the chip could take for the work those kernels
are there for, over the device time a step of their calls in the reduced
trace.

The least time (``costs_hybrid_seq.py``) is the algorithm's, not this
implementation's call list: causal attention's forward pass once and its
backward pass once an attention layer (recomputing a layer, or splitting
the backward into a dq and a dk/dv call that each work the scores out
again, adds device time and no need), and the grouped product's two
products forward and four backward an expert layer, at the rows the
placement counted as routed to the held experts (its counters
``routed_rows_layer_<i>`` over ``routed_batches``: a probe of the last
batches of the window, so numerator and traced time follow the same
routing). Each part is the larger of its operations over the bf16 peak
and its bytes over the HBM peak. So a change that saves a recomputed
call raises the share only by the device time it saves.

Which operations those are was read from a trace by hand
(``tools/describe_trace.py``, two steps of the cell; PERF.md, Findings).
``trace_reduce.reduce`` groups operations by their instruction's name,
and the compiler names a Pallas call after the innermost scope around
it, not ``custom-call`` (that group holds only ``AllocateBuffer``, 0 s):
the flash calls read ``flash_attention`` (the scope
``GroupedQueryAttention`` puts around them: 4 a step, 40 ms), the
grouped product's ``gmm`` (24 a step) and ``tgmm`` (8 a step), the jitted
functions of JAX's megablox that hold them. One metric for all of them,
as ISSUE 29 names it. A cell whose step runs none of them, or whose
placement counts no routed rows, has nothing to read here.
"""

import costs_hybrid_seq
from weights_hybrid_seq import sizes_of

GROUPS = ("flash_attention", "gmm", "tgmm")


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    seconds = sum(s for group, s in r.trace["ops"] if group in GROUPS)
    probed = r.counters.get("routed_batches")
    if seconds <= 0 or not probed:
        return None
    sz = sizes_of(r.config)
    length = min(r.env.mix["session_length"], r.batch)
    rows = [r.counters[f"routed_rows_layer_{i}"] / probed
            for i in range(sz["pattern"].count("E"))]
    least = (costs_hybrid_seq.flash_least_seconds(
        sz, length, r.batch // length, r.peaks)
        + costs_hybrid_seq.grouped_least_seconds(sz, rows, r.peaks))
    return 100.0 * least * r.trace["steps"] / seconds
