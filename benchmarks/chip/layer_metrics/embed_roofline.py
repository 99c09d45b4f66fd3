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
nothing of the tower has. A cell whose placement names no table shapes
has nothing to read here.
"""


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    per_step = r.trace["table_s"] / r.trace["steps"]
    if per_step <= 0:
        return None
    least = r.embed_min_bytes / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least / per_step
