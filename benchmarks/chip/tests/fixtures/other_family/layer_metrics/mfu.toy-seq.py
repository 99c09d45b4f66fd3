"""The whole step's share of the chips' bf16 peak for the toy tower: the
one matrix product's operations per item event (forward and backward,
2 operations a multiply-accumulate) times the traced window's events
per second, over chips times the peak."""


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    flops = 3 * 2 * r.config["width"] * r.config["vocab"]
    events_per_s = r.trace["steps"] * r.batch / r.trace["window_s"]
    return (100.0 * flops * events_per_s
            / (r.chips * r.peaks["bf16_flops_per_s"]))
