"""Operations and bytes that the algorithm needs, from the configuration's
widths and a batch's ids: the same work whatever implements it."""

import json
import os

import numpy as np

from weights import mlp_shapes


def tower_macs_per_sample(config):
    """Multiply-accumulates of one forward pass of the tower: the two
    MLPs and the lower-triangle pairwise dot of tables+1 fields."""
    fields = len(config["table_cardinalities"]) + 1
    pairs = fields * (fields - 1) // 2
    return (sum(fan_in * fan_out for _, fan_in, fan_out in mlp_shapes(config))
            + pairs * config["embedding_dim"])


def train_flops_per_sample(config):
    """Forward and backward: 2 operations a MAC, backward twice forward."""
    return 3 * 2 * tower_macs_per_sample(config)


def embed_min_bytes(unique_rows, batch, config):
    """Least bytes a step's embedding work moves: for every distinct row
    a batch touches, value and accumulator read and written (float32);
    the pooled output written forward and its gradient read backward in
    the compute type."""
    dim, tables = config["embedding_dim"], len(config["table_cardinalities"])
    compute_bytes = {"bfloat16": 2, "float32": 4}[config["compute_dtype"]]
    return (int(unique_rows) * dim * 4 * 4
            + 2 * int(batch) * tables * dim * compute_bytes)


def unique_rows(row_ids):
    """Distinct (table, row) pairs of a (batch, tables) row matrix."""
    return int(sum(len(np.unique(row_ids[:, t]))
                   for t in range(row_ids.shape[1])))


def peaks_for(root, device_kind):
    with open(os.path.join(root, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json: add it with its source")
    return table[device_kind]
