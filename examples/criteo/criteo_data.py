"""Criteo click-logs loader (Kaggle DAC / Terabyte format).

The reference's workloads are all Criteo DLRM shapes; this loader feeds
them: each line is ``label \t I1..I13 \t C1..C26`` (ints may be empty,
categoricals are 8-hex-digit strings or empty). Dense features use the
standard log(1+x) transform; each categorical token parses to a u64
(hex value, or its first 8 raw bytes when not hex) and is mixed with
FarmHash64 into the sign space (column separation comes from the
schema's ``feature_index_prefix_bit``, like the reference's
adult-income config).

Works streaming from plain or .gz files; ``synthetic_batches`` generates
the same shape without the dataset for tests/smoke runs.
"""

import gzip
import os
from typing import Iterator, Optional

import numpy as np

from persia_tpu.data.batch import (
    IDTypeFeatureWithSingleID,
    Label,
    NonIDTypeFeature,
    PersiaBatch,
)
from persia_tpu.hashing import farmhash64_np

NUM_DENSE = 13
NUM_SLOTS = 26
SLOT_NAMES = [f"C{i + 1}" for i in range(NUM_SLOTS)]


def _open(path: str):
    if path.endswith(".gz"):
        return gzip.open(path, "rt")
    return open(path, "r")


def _token_to_u64(t: str) -> int:
    """One categorical token -> raw u64 (0 = missing). Criteo tokens are
    8 hex chars; tolerate anything else (corrupt lines, other datasets)
    by packing the first 8 raw bytes instead of crashing mid-stream."""
    if not t:
        return 0
    try:
        return int(t, 16) & 0xFFFFFFFFFFFFFFFF
    except ValueError:
        return int.from_bytes(t.encode()[:8].ljust(8, b"\0"), "little")


def _hash_token_matrix(rows) -> np.ndarray:
    """Categorical tokens -> u64 signs, one vectorized pass per BATCH
    (per-line numpy dispatch would cap the loader far below the pipeline
    rate on Criteo-1TB). The token's u64 value (parsed hex, or raw bytes
    for non-hex) is mixed with FarmHash64 so the sign space matches the
    routing hash; empty tokens map to sign 0 ("missing")."""
    n = len(rows)
    count = n * NUM_SLOTS
    flat_vals = np.fromiter(
        (_token_to_u64(t) for row in rows for t in row),
        dtype=np.uint64, count=count)
    mask = np.fromiter(
        (bool(t) for row in rows for t in row), dtype=bool, count=count)
    out = np.zeros(count, dtype=np.uint64)
    if mask.any():
        out[mask] = farmhash64_np(flat_vals[mask]) | np.uint64(1)  # != 0
    return out.reshape(n, NUM_SLOTS)


def criteo_batches(
    path: str,
    batch_size: int = 4096,
    max_samples: Optional[int] = None,
    requires_grad: bool = True,
    replica_index: int = 0,
    replica_size: int = 1,
) -> Iterator[PersiaBatch]:
    """Stream PersiaBatches from a Criteo tsv(.gz) file.

    ``replica_index/replica_size`` shard the stream by whole batches of
    lines BEFORE parsing, so N loader replicas split both the data and
    the parse/hash cost (filtering built batches afterwards would make
    every replica pay the full transform cost for 1/N of the output)."""
    labels, dense_rows, cat_rows = [], [], []
    batch_id = 0
    produced = 0
    line_idx = 0

    def flush():
        nonlocal labels, dense_rows, cat_rows, batch_id
        n = len(labels)
        dense = np.log1p(np.maximum(
            np.array(dense_rows, dtype=np.float32), 0.0))
        cats = _hash_token_matrix(cat_rows)  # (n, 26) u64
        batch = PersiaBatch(
            [IDTypeFeatureWithSingleID(
                SLOT_NAMES[i], np.ascontiguousarray(cats[:, i]))
             for i in range(NUM_SLOTS)],
            non_id_type_features=[NonIDTypeFeature(dense)],
            labels=[Label(np.array(labels, np.float32).reshape(n, 1))],
            requires_grad=requires_grad,
            batch_id=batch_id,
        )
        labels, dense_rows, cat_rows = [], [], []
        batch_id += 1
        return batch

    with _open(path) as f:
        for line in f:
            if max_samples is not None and line_idx >= max_samples:
                break
            owned = ((line_idx // batch_size) % replica_size
                     == replica_index)
            line_idx += 1
            if not owned:
                continue  # another replica's batch: skip before parsing
            parts = line.rstrip("\n").split("\t")
            if len(parts) != 1 + NUM_DENSE + NUM_SLOTS:
                continue  # malformed line
            labels.append(float(parts[0]))
            dense_rows.append(
                [float(x) if x else 0.0 for x in parts[1:1 + NUM_DENSE]])
            cat_rows.append(parts[1 + NUM_DENSE:])  # raw tokens; hashed
            produced += 1                           # per batch in flush()
            if len(labels) == batch_size:
                yield flush()
    if labels:
        yield flush()


# Synthetic Criteo-shaped streams live in the workload zoo now
# (persia_tpu/workloads/generator.py) — the examples, tests and the e2e
# bench all train the ONE shared definition. The historical names stay
# importable here, draw-order bit-compatible with the old local
# implementations; `persia_tpu.workloads.generator.dlrm_batches` is the
# production-shaped (zipf, mixed-dim) variant the e2e bench drives.
from persia_tpu.workloads.generator import (  # noqa: E402,F401
    criteo_learnable_batches as learnable_batches,
    criteo_uniform_batches as synthetic_batches,
    hidden_weight as _hidden_weight,
)


def write_synthetic_tsv(path: str, num_samples: int, seed: int = 0):
    """A tiny Criteo-format file (for tests of the parsing path)."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(num_samples):
            label = int(rng.random() < 0.25)
            ints = [
                "" if rng.random() < 0.1 else str(int(rng.integers(0, 1000)))
                for _ in range(NUM_DENSE)
            ]
            cats = [
                "" if rng.random() < 0.1
                else format(int(rng.integers(0, 1 << 32)), "08x")
                for _ in range(NUM_SLOTS)
            ]
            f.write("\t".join([str(label), *ints, *cats]) + "\n")
