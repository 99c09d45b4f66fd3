"""Generator kind ``item_histories``: lifelong user histories of item ids
from one vocabulary, each event with the event that follows it as its
target. ``batch`` counts item events (one target each), which is what a
step consumes: ``batch // T`` histories of ``T = min(session_length,
batch)`` events, laid end to end in two flat arrays, ``items`` and
``targets`` (``batch`` long, int64, 0-based ids), so that whoever cuts
every array of a batch to its first half gets whole events.

An event's item is drawn zipf(``alpha``) over the vocabulary (id 0 the
hottest, ``traffic.RankLaw``, the law of the accepted mix) or, with
probability ``follow_share``, follows from the two before it as
``(3 a + b + 1) mod V`` (``a`` the last, ``b`` the one before), so there
is a task to learn. No padding: every history fills its window. The
vocabulary is the configuration's ``vocab_size`` less the one row a
device table keeps for padding. Batch ``i`` of seed ``s`` is a pure
function of (mix, config, batch, s, i).
"""

import numpy as np

from traffic import RankLaw


class Histories:
    def __init__(self, mix, config, batch, seed):
        self.items = int(config["vocab_size"]) - 1
        self.law = RankLaw(self.items, mix["alpha"], mix["head_ranks"])
        self.follow = float(mix["follow_share"])
        self.batch_size, self.seed = int(batch), int(seed)
        self.length = min(int(mix["session_length"]), self.batch_size)
        if self.batch_size % self.length:
            raise ValueError(f"batch {batch} is no whole number of "
                             f"histories of {self.length}")

    def batch(self, i):
        rng = np.random.default_rng([self.seed, 0x415, int(i)])
        n, t = self.batch_size // self.length, self.length + 1
        seq = self.law.ranks(rng, (n, t))
        follows = rng.random((n, t)) < self.follow
        for at in range(2, t):      # an event may follow followed events
            seq[:, at] = np.where(
                follows[:, at],
                (3 * seq[:, at - 1] + seq[:, at - 2] + 1) % self.items,
                seq[:, at])
        return {"index": int(i), "items": seq[:, :-1].reshape(-1),
                "targets": seq[:, 1:].reshape(-1)}


def stream(mix, config, batch, seed):
    return Histories(mix, config, batch, seed)
