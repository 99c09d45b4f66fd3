"""Device-resident sharded embedding tables — the TPU-first sparse mode.

The CUDA reference keeps all embeddings on CPU parameter servers because
GPU HBM is too small for 100T parameters. On TPU pods, a second mode is
natural: hash the sign space into a fixed-vocab table that lives in HBM,
sharded row-wise over the mesh's ``model`` axis. Lookup is a gather that
XLA turns into collective-permute traffic over ICI — no host round-trip
at all.

The modules have two entries. Called with ids alone they gather from
their own tables, and ordinary autodiff gives a table-shaped gradient
(zeros plus a scatter-add): right for a caller that wants ``jax.grad``
over the tables. Called with ``rows=``, the values already gathered at
:func:`table_row_index`, they only pool them, so a train step can
differentiate with respect to the few rows a batch touches and never
build the table-shaped gradient. :func:`distinct_rows` and
:func:`update_touched_rows` are the rest of that step: the distinct rows
of a batch (their gradients summed onto them by the caller), the
caller's optax transformation applied to those rows and to the same rows
of its table-shaped state, and the results written back in place
(``make_device_mode_trainer`` uses them whenever that is exact).

Use this mode when the (hashed) vocab fits in pod HBM; use the CPU
parameter-server mode for beyond-HBM scale. Both share the worker
preprocessing (dedup/prefix) and the model zoo.
"""

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax import lax

from persia_tpu.parallel.mesh import MODEL_AXIS


def table_row_index(ids: jnp.ndarray, vocab_size: int):
    """(row, mask) of raw ids in a ``vocab_size``-row table: id 0 is
    padding, masked out and pointed at row 0, which no real id hashes
    to."""
    mask = ids > 0
    return ((ids % (vocab_size - 1)) + 1) * mask, mask


class DeviceEmbeddingBag(nn.Module):
    """One hashed embedding table with sum/mean pooling, or none.

    ids enter as the worker's static-shape (bs, sample_fixed_size) index
    tensor of raw u64 signs hashed modulo ``vocab_size`` (0 rows are
    reserved for padding via the mask argument). ``rows`` is the
    (bs, sample_fixed_size, dim) the caller gathered at those ids itself;
    the table is then not touched. ``pooling="none"`` is the sequence
    slot: the gathered rows as they stand, padding zeroed, and their
    (bs, sample_fixed_size) mask, for a tower that reads a history
    position by position.
    """

    vocab_size: int
    dim: int
    compute_dtype: Any = jnp.bfloat16
    pooling: str = "sum"  # "sum" | "mean" | "none"

    @nn.compact
    def __call__(self, hashed_ids: jnp.ndarray, mask: jnp.ndarray,
                 rows=None):
        # the name a trace knows table work by; the backward scatter
        # carries it too, under transpose(jvp(...))
        with jax.named_scope("tables_gather"):
            if rows is None:
                table = self.param(
                    "table",
                    nn.with_partitioning(
                        nn.initializers.uniform(scale=0.01),
                        (MODEL_AXIS, None)
                    ),
                    (self.vocab_size, self.dim),
                    jnp.float32,
                )
                rows = jnp.take(table, hashed_ids, axis=0)  # (bs, sfs, dim)
            gathered = rows * mask[..., None].astype(rows.dtype)
            if self.pooling == "none":
                return gathered.astype(self.compute_dtype), mask
            pooled = gathered.sum(axis=1)
            if self.pooling == "mean":
                denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1)
                pooled = pooled / denom
            return pooled.astype(self.compute_dtype)


class DeviceEmbeddingCollection(nn.Module):
    """All slots' device tables, producing the model-ready embedding list.

    ``slot_specs`` is a sequence of (name, vocab_size, dim). Input is a
    dict name -> (bs, sfs) int32/uint32 hashed id tensor; id 0 = padding.
    ``rows``, if given, mirrors this module's parameters
    (:func:`table_rows`) and holds each table's gathered values.
    """

    slot_specs: Sequence[Any]
    compute_dtype: Any = jnp.bfloat16
    pooling: str = "sum"

    @nn.compact
    def __call__(self, id_tensors, rows=None):
        out = []
        for name, vocab, dim in self.slot_specs:
            hashed, mask = table_row_index(id_tensors[name], vocab)
            bag = DeviceEmbeddingBag(
                vocab_size=vocab, dim=dim, compute_dtype=self.compute_dtype,
                pooling=self.pooling, name=f"bag_{name}",
            )
            out.append(bag(hashed, mask, None if rows is None
                           else rows[f"bag_{name}"]["table"]))
        return out


def table_rows(slot_specs, id_tensors):
    """The row of its table each id reads, as a tree that mirrors the
    parameters of a :class:`DeviceEmbeddingCollection`."""
    return {f"bag_{name}": {"table": table_row_index(id_tensors[name],
                                                     vocab)[0]}
            for name, vocab, _ in slot_specs}


# --- the touched-rows update ---------------------------------------------

_INT32_MAX = np.iinfo(np.int32).max


def distinct_rows(index: jnp.ndarray, beyond: jnp.ndarray):
    """The distinct values of each row of ``index`` (T, N) int32, at a
    static size, by sorting (one batched sort serves T tables).

    Returns ``(touched, slot)``, both (T, N): ``touched[t]`` holds the
    distinct values ascending, then padding ``beyond[t] + k`` at position
    k, so it is strictly increasing throughout and a scatter through it
    may promise unique indices and drop what is out of range
    (``beyond[t]``: the table's row count); ``slot[t, i]`` is where
    ``index[t, i]`` stands in ``touched[t]``.
    """
    place = lax.broadcasted_iota(jnp.int32, index.shape, 1)
    ordered, origin = lax.sort((index, place), dimension=1, num_keys=1)
    first = jnp.concatenate(
        [jnp.ones_like(ordered[:, :1], bool),
         ordered[:, 1:] != ordered[:, :-1]], axis=1)
    rank = jnp.cumsum(first, axis=1, dtype=jnp.int32) - 1
    _, slot = lax.sort((origin, rank), dimension=1, num_keys=1)
    heads = lax.sort(jnp.where(first, ordered, _INT32_MAX), dimension=1)
    touched = jnp.where(heads == _INT32_MAX, beyond[:, None] + place, heads)
    return touched, slot


def update_touched_rows(optimizer: optax.GradientTransformation, params,
                        opt_state, grads, touched):
    """One step of ``optimizer`` that reads and writes only the rows
    ``touched`` names.

    ``touched`` mirrors ``params``: ``False`` at a leaf that is updated
    whole, else a row of :func:`distinct_rows`' ``touched``, and ``grads``
    holds one gradient for each of its entries there, (N, dim) in place
    of the leaf's (rows, dim). The transformation sees a small tree: those
    rows of the parameters and of every state leaf that mirrors them, and
    everything else as it is. That equals the whole-table step only if a
    row's update needs nothing of other rows and a zero gradient changes
    nothing (:func:`rows_suffice`). Returns ``(params, opt_state)``.
    """
    def rows_of(leaf, at):
        if at is False:
            return leaf
        with jax.named_scope("row_update"):
            # padding reads some real row; its gradient is zero and its
            # result is dropped
            return jnp.take(leaf, at, axis=0, mode="clip")

    # the indices are sorted too, but that promise buys a slower scatter
    # on the v5e: 1.68 ms against 0.30 for 4096 rows of 512 bytes
    # (PERF.md, PR 26)
    distinct = dict(mode="drop", unique_indices=True)

    def put(rows, leaf, at):
        if at is False:
            return rows
        with jax.named_scope("row_update"):
            return leaf.at[at].set(rows, **distinct)

    def add(leaf, update, at):
        if at is False:
            return optax.apply_updates(leaf, update)
        with jax.named_scope("row_update"):
            return leaf.at[at].add(update.astype(leaf.dtype), **distinct)

    state_at = optax.tree_utils.tree_map_params(
        optimizer, lambda _, at: at, opt_state, touched,
        transform_non_params=lambda _: False)
    updates, small_state = optimizer.update(
        grads, jax.tree.map(rows_of, opt_state, state_at),
        jax.tree.map(rows_of, params, touched))
    return (jax.tree.map(add, params, updates, touched),
            jax.tree.map(put, small_state, opt_state, state_at))


def rows_suffice(optimizer: optax.GradientTransformation) -> bool:
    """Whether :func:`update_touched_rows` gives what ``optimizer`` gives
    over whole tables, found by trying it on a table of six rows (a
    concrete answer under a trace too; one small compilation).

    Two things have to hold. A zero gradient is a fixed point: after a
    step that moved the table, a step with no gradient changes neither a
    parameter nor a state leaf that mirrors one, exactly (Adagrad, plain
    SGD; not Adam, momentum, weight decay, noise). And rows do not lean
    on each other: two steps over changing subsets of rows agree with the
    same steps over the whole table (not a trust ratio over a leaf's
    norm).
    """
    rows = np.linspace(-1.0, 1.0, 12, dtype=np.float32).reshape(6, 2) + 0.03
    steps = ((np.array([1, 3, 6, 7], np.int32),      # rows 1, 3; padding
              np.array([[0.5, -0.25], [0.125, 1.0], [0, 0], [0, 0]],
                       np.float32)),
             (np.array([0, 1, 4, 6], np.int32),      # row 3 left alone now
              np.array([[-1.0, 0.5], [0.75, 0.25], [2.0, -0.5], [0, 0]],
                       np.float32)))

    def mirrors(state):
        out = []
        optax.tree_utils.tree_map_params(optimizer, out.append, state)
        return out

    def same(a, b, exact):
        return jnp.all(a == b) if exact else jnp.allclose(a, b, rtol=1e-5,
                                                          atol=1e-7)

    def probe():
        table = jnp.asarray(rows)
        whole = by_row = (table, optimizer.init(table))
        if any(m.shape != table.shape for m in mirrors(whole[1])):
            return jnp.asarray(False)   # a state that has no rows to take
        for at, g in steps:
            dense = jnp.zeros_like(table).at[at].add(g, mode="drop")
            updates, state = optimizer.update(dense, whole[1], whole[0])
            whole = (optax.apply_updates(whole[0], updates), state)
            by_row = update_touched_rows(optimizer, *by_row, jnp.asarray(g),
                                         jnp.asarray(at))
        agree = [same(a, b, False) for a, b in zip(
            [whole[0]] + mirrors(whole[1]), [by_row[0]] + mirrors(by_row[1]))]
        updates, state = optimizer.update(jnp.zeros_like(table), whole[1],
                                          whole[0])
        still = [same(a, b, True) for a, b in zip(
            [optax.apply_updates(whole[0], updates)] + mirrors(state),
            [whole[0]] + mirrors(whole[1]))]
        return jnp.all(jnp.stack(agree + still))

    with jax.core.eval_context():   # an answer now, under a caller's trace too
        return bool(jax.jit(probe)())
