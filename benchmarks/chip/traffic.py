"""Traffic: a mix file of parameters in, a seed-deterministic stream of
batches out. A mix names its generator by ``kind``: ``dlrm_stream`` is
the one in this file; any other kind is ``generators/<kind>.py``, found
by name as placements and readers are.

A ``dlrm_stream`` batch is plain numpy: ``ids`` (batch, tables) int64,
0-based id per table (the popularity rank: id 0 is the hottest),
``dense`` (batch, num_dense) float32, ``label`` (batch, 1) float32.
Batch ``i`` of seed ``s`` is a pure function of (mix, cardinalities, s,
i), so any thread may make any batch and the stream is the same. A
generator of another kind promises the same of its own arrays.

Copied from ``persia_tpu/workloads/generator.py`` (``zipf_cdf``,
``zipf_ranks``, ``hidden_weight``, ``dlrm_batches``) so that a later PR
cannot change the yardstick by changing the program; what differs is
listed in PERF.md.
"""

import collections
import concurrent.futures
import importlib.util
import json
import os

import numpy as np

_U64 = np.uint64
OWN_KIND = "dlrm_stream"


def generator_path(mix_path, kind):
    """``<benchmark>/generators/<kind>.py`` for a mix under
    ``<benchmark>/mixes/``."""
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(mix_path)))
    return os.path.join(bench_dir, "generators", f"{kind}.py")


def load_mix(path):
    """The mix's parameters, with ``generator`` set to the file that
    draws it where that is not this one."""
    with open(path) as f:
        mix = json.load(f)
    kind = mix.get("kind")
    if kind == OWN_KIND:
        if mix.get("id_law") != "zipf":     # alpha 0 is the uniform law
            raise ValueError(f"{path}: unknown id_law {mix.get('id_law')!r}")
        return mix
    generator = generator_path(path, kind)
    if not isinstance(kind, str) or not os.path.exists(generator):
        raise ValueError(f"{path}: unknown mix kind {kind!r}: no generator "
                         f"{generator}")
    return dict(mix, generator=generator)


def stream_for(mix, config, batch, seed):
    """The mix's stream over a configuration: an object whose
    ``batch(i)`` gives a dict with ``index`` and arrays whose first axis
    is ``batch``, a pure function of (mix, config, batch, seed, i)."""
    if mix["kind"] == OWN_KIND:
        return Stream(mix, config["table_cardinalities"],
                      config["num_dense"], batch, seed)
    spec = importlib.util.spec_from_file_location(
        "bench_generator_" + mix["kind"], mix["generator"])
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.stream(mix, config, batch, seed)


class RankLaw:
    """Truncated zipf(alpha) over ranks 1..vocab, drawn by inverse CDF:
    exact over the first ``head`` ranks, and beyond them the midpoint-rule
    power law (mass of rank r = integral of x^-alpha over r-0.5..r+0.5,
    which differs from r^-alpha by a relative alpha(alpha+1)/(24 r^2))."""

    def __init__(self, vocab, alpha, head):
        self.vocab, self.alpha = int(vocab), float(alpha)
        self.head = min(int(head), self.vocab)
        p = np.arange(1, self.head + 1, dtype=np.float64) ** -self.alpha
        self.head_cum = np.cumsum(p)
        self.head_mass = float(self.head_cum[-1])
        self.tail_mass = (self._integral(self.head + 0.5, self.vocab + 0.5)
                          if self.vocab > self.head else 0.0)

    def _integral(self, a, b):
        if self.alpha == 1.0:
            return float(np.log(b / a))
        e = 1.0 - self.alpha
        return float((b ** e - a ** e) / e)

    def ranks(self, rng, size):
        """0-based ranks."""
        u = rng.random(size) * (self.head_mass + self.tail_mass)
        out = np.searchsorted(self.head_cum, u).clip(max=self.head - 1)
        tail = u >= self.head_mass
        if self.tail_mass and tail.any():
            t = u[tail] - self.head_mass
            a = self.head + 0.5
            if self.alpha == 1.0:
                x = a * np.exp(t)
            else:
                e = 1.0 - self.alpha
                x = (a ** e + t * e) ** (1.0 / e)
            out[tail] = np.clip(np.floor(x + 0.5), self.head + 1,
                                self.vocab).astype(np.int64) - 1
        return out.astype(np.int64)


def hidden_weight(stream, ids):
    """Deterministic ~N(0,1) weight per (stream, id): splitmix64 mixing
    and Box-Muller. Independent of the seed: it defines the task."""
    x = (ids.astype(np.uint64) * _U64(0x9E3779B97F4A7C15)
         + (np.asarray(stream, np.uint64) + _U64(1))
         * _U64(0xBF58476D1CE4E5B9))

    def mix(v):
        v = v ^ (v >> _U64(30))
        v = v * _U64(0xBF58476D1CE4E5B9)
        v = v ^ (v >> _U64(27))
        v = v * _U64(0x94D049BB133111EB)
        return v ^ (v >> _U64(31))

    h1 = mix(x)
    h2 = mix(x ^ _U64(0xD6E8FEB86659FD93))
    u1 = ((h1 >> _U64(11)).astype(np.float64) + 1.0) / (2.0**53 + 2)
    u2 = (h2 >> _U64(11)).astype(np.float64) / 2.0**53
    return np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)


class Stream:
    """``batch(i)`` for the mix over the configuration's tables."""

    def __init__(self, mix, cardinalities, num_dense, batch, seed):
        self.mix, self.batch_size, self.seed = mix, int(batch), int(seed)
        self.cards = [int(c) for c in cardinalities]
        self.num_dense = int(num_dense)
        self.laws = [RankLaw(c, mix["alpha"], mix["head_ranks"])
                     for c in self.cards]
        self.dense_w = hidden_weight(
            np.arange(self.num_dense, dtype=np.uint64) + _U64(1 << 20),
            np.full(self.num_dense, 7, np.uint64)) * 0.5

    def batch(self, i):
        rng = np.random.default_rng([self.seed, 0xD12, int(i)])
        n, tables = self.batch_size, len(self.cards)
        ids = np.empty((n, tables), dtype=np.int64)
        for t in range(tables):
            ids[:, t] = self.laws[t].ranks(rng, n)
        dense = np.log1p(np.abs(rng.normal(
            size=(n, self.num_dense)))).astype(np.float32)
        logits = hidden_weight(
            np.arange(tables, dtype=np.uint64)[None, :],
            ids.astype(np.uint64)).sum(axis=1) / np.sqrt(tables)
        logits += dense.astype(np.float64) @ self.dense_w
        std = float(logits.std()) or 1.0
        noisy = logits + rng.normal(
            0.0, self.mix["label_noise"] * std, size=n)
        prob = 1.0 / (1.0 + np.exp(-2.5 * noisy / std))
        label = (rng.random(n) < prob).astype(np.float32).reshape(n, 1)
        return {"index": int(i), "ids": ids, "dense": dense, "label": label}


class Prefetcher:
    """Batches in order from background threads, at most ``depth`` ahead.
    ``convert`` (the placement's, optional) runs in the worker thread and
    turns the plain batch into what the placement's step takes."""

    def __init__(self, stream, threads, depth, convert=None, start=0):
        self._stream, self._convert = stream, convert
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=int(threads), thread_name_prefix="bench-gen")
        self._pending = collections.deque()
        self._next = int(start)
        self._depth = int(depth)
        self._fill()

    def _make(self, i):
        b = self._stream.batch(i)
        return self._convert(b) if self._convert else b

    def _fill(self):
        while len(self._pending) < self._depth:
            self._pending.append(self._pool.submit(self._make, self._next))
            self._next += 1

    def ready(self):
        """Whether the next batch is already made (a late generator shows
        as a False here and as time in the caller's data_wait span)."""
        return self._pending[0].done()

    def get(self):
        out = self._pending.popleft().result()
        self._fill()
        return out

    def close(self):
        for f in self._pending:
            f.cancel()
        self._pool.shutdown(wait=True, cancel_futures=True)

