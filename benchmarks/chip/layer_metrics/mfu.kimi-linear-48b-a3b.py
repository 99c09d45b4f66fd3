"""The whole step's share of the chips' bf16 peak for the delta-rule
sequence tower: operations the forward and backward passes require an
item event (``costs_kda_seq.py``, from the configuration's widths at the
mix's ``session_length``: the Kimi Delta Attention layers' projections
and their recurrence as the position-by-position form needs it, the
latent attention layer's projections and its causal scores at the key
width 192 and values at 128, the dense feed-forward, shared and routed
experts, these at the rows they are expected to see, and the head;
recomputation not counted) times the traced window's events per second,
over chips times the peak from ``peaks.json``."""

import costs_kda_seq


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    events_per_s = r.trace["steps"] * r.batch / r.trace["window_s"]
    flops = costs_kda_seq.train_flops_per_event(
        r.config, r.env.mix["session_length"])
    return (100.0 * flops * events_per_s
            / (r.chips * r.peaks["bf16_flops_per_s"]))
