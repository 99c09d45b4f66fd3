"""The embedding work's share of its HBM roofline: the least time the
chip could take (bytes the algorithm needs per step, ``costs.embed_min_
bytes``: for every distinct row a batch touches, value and accumulator
read and written, plus the pooled output written and its gradient read,
over the HBM peak) over the device time per step of the operations that
do the tables' work. Bound: HBM bandwidth.

Which operations those are is ``trace_reduce.on_tables``'s rule, written
after looking at a trace by hand (PERF.md, Findings): an operation counts
when its HLO text has an operand or a result of a table's shape, which
the gather, the gradient's scatter-add and the row optimizer have and
nothing of the tower has. A cell whose placement names no table shapes,
or no rows that a batch touches, has nothing to read here. The bytes are
a DLRM configuration's (``embedding_dim``, ``table_cardinalities``,
``compute_dtype``), which is why this metric lists its cells.
"""

import costs


def read(r):
    if (r.trace is None or r.peaks is None or not r.trace["steps"]
            or r.unique_rows is None):
        return None
    per_step = r.trace["table_s"] / r.trace["steps"]
    if per_step <= 0:
        return None
    least = (costs.embed_min_bytes(r.unique_rows, r.batch, r.config)
             / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / per_step
