"""The whole step's share of the chips' bf16 peak for the sliding-window
sequence tower: operations the forward and backward passes require an
item event (``costs_window_seq.py``, from the configuration's widths at
the mix's ``session_length``: the attention projections, the full
layers' scores and values over every causal pair, the window layers'
over the band's pairs, ``min(t + 1, window)`` a query and no more,
whatever an implementation walks; the router and the held experts at the
rows they are expected to see; the head; recomputation not counted)
times the traced window's events per second, over chips times the peak
from ``peaks.json``."""

import costs_window_seq


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    events_per_s = r.trace["steps"] * r.batch / r.trace["window_s"]
    flops = costs_window_seq.train_flops_per_event(
        r.config, r.env.mix["session_length"])
    return (100.0 * flops * events_per_s
            / (r.chips * r.peaks["bf16_flops_per_s"]))
