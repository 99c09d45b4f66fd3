"""Generator kind ``item_sessions``: histories of item ids from one
vocabulary with the next item as the target. ``batch`` counts item
events (one target each), which is what a step consumes. Batch ``i`` of
seed ``s`` is a pure function of (mix, config, batch, s, i)."""

import numpy as np


class Sessions:
    def __init__(self, mix, config, batch, seed):
        self.skew = float(mix["skew"])
        self.vocab, self.context = int(config["vocab"]), int(config["context"])
        self.batch_size, self.seed = int(batch), int(seed)

    def batch(self, i):
        rng = np.random.default_rng([self.seed, 0x5E55, int(i)])
        u = rng.random((self.batch_size, self.context))
        history = np.floor(self.vocab * u ** self.skew).astype(np.int64)
        # the next item follows from the last two, so there is a task
        target = (3 * history[:, -1] + history[:, -2] + 1) % self.vocab
        return {"index": int(i), "history": history, "target": target}


def stream(mix, config, batch, seed):
    return Sessions(mix, config, batch, seed)
