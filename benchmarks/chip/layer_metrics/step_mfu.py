"""The whole step's share of the chips' bf16 peak: operations that the
tower's forward and backward passes require per sample (``costs.py``,
from the configuration's widths) times the traced window's samples per
second, over chips times the peak from ``peaks.json``."""

import costs


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    samples_per_s = r.trace["steps"] * r.batch / r.trace["window_s"]
    return (100.0 * costs.train_flops_per_sample(r.config) * samples_per_s
            / (r.chips * r.peaks["bf16_flops_per_s"]))
