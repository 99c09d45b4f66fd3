"""A learned indexer's part of selected attention (the lightning indexer
of the DeepSeek-V3.2-Exp report): the index scores of every causal
(query, key), the top-k selection a query, and the indexer's alignment
loss with its gradient. By tiles of queries, so that no ``(heads, T,
T)`` array ever stands whole: a tile of ``tile`` queries holds one
``(tile, T)`` float32 of index scores (16 MB at 512 queries and 8192
keys) and, for the target, the attention scores of one key-value head's
group of query heads at a time.

``index_scores``: ``I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s])``,
one key for all the index heads; products on the operands' dtype with
float32 sums, everything after them float32. Two Pallas kernels under a
``jax.custom_vjp`` where the shapes admit their blocks
(:func:`index_blocks`; the shapes alone decide): a head's ``(queries,
keys)`` products live in VMEM only, forward and backward, and the key
blocks after the tile's last query are neither fetched nor multiplied.
``index_scores_plain`` is the ``jax.numpy`` form, the kernels' oracle
and the path of every other shape: under ``jax.vjp`` its ``(index
heads, tile, T)`` float32 products stand in HBM (268 MB at 16 heads,
512 queries, 8192 keys), are read back, and make a ``ds`` of that size.

``select_keys``: for every query ``t`` the ``min(t + 1, topk)`` keys ``s
<= t`` with the largest ``I[t, s]``, as an int8 ``(batch, T, T)`` array
of ones and zeros. A row's threshold, its k-th largest score, is found
by bisection on the float32 order (the scores' bits made an unsigned
integer that sorts as they do: 32 passes of a comparison and a count,
where a sort of 8192 keys a row moves them a dozen times); the keys
above it are taken, and of those equal to it the earliest, as many as
the count still needs, which is the order ``lax.top_k`` gives equal
scores. ``-0.0`` counts as ``0.0``. No gradient: callers hand it
``stop_gradient``'s outputs.

``alignment_loss``: ``mean_t sum_{s in S_t} p log(p / r)`` with ``p[t,
.]`` the attention probabilities over the selected keys summed over the
heads and divided by their number (rebuilt from the queries, the keys
and the flash kernel's ``lse``; a constant), and ``r[t, .] = softmax
over S_t of I[t, .]``. Its gradient reaches ``q_i``, ``k_i`` and ``w``
only, and is ``(r sum_s p - p) / queries`` a score: the forward rule
works it out in the same pass over the tiles that sums the loss, names
it (``RESIDUAL_NAMES``) and the backward rule only scales it, so under
a ``jax.checkpoint`` whose policy keeps that name, and the selection's,
a training step makes one pass over the target and none to recompute
the selection.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from persia_tpu import ops

F32 = jnp.float32

# the selection, and the alignment loss's gradients to q_i, k_i and w:
# what a ``jax.checkpoint`` policy keeps (``save_only_these_names``) so
# that neither is made twice a step
RESIDUAL_NAMES = ("index_selection", "index_alignment_grads")


def tile_of(t, most):
    """The largest divisor of ``t`` that is at most ``most``."""
    return max(d for d in range(1, min(t, most) + 1) if t % d == 0)


def index_scores_plain(q_i, k_i, w):
    """``I`` of a tile of queries against every key: ``q_i`` (batch,
    tile, heads, width), ``k_i`` (batch, T, width), ``w`` (batch, tile,
    heads) float32 -> (batch, tile, T) float32. Not masked. The plain
    form: the kernels' oracle, and the path of shapes no block divides;
    under ``jax.vjp`` the ``(batch, heads, tile, T)`` products stand in
    HBM."""
    s = jnp.einsum("bqhd,bkd->bhqk", q_i, k_i, preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(s) * jnp.moveaxis(w, 2, 1)[..., None],
                   axis=1)


# --- the index scores and their pullback as kernels --------------------------

# queries and keys a grid step: a whole tile of queries where it is no
# longer, and the widest block of keys that divides T. Alone on a v5e at
# 16 x 64 heads, T 8192 (PERF.md §6 "PR 42"), 512 x 512 ran a layer's
# pullback in 3.27 ms, 256 x 512 in 3.36, 512 x 256 in 3.37, 256 x 256
# in 4.01, 256 x 1024 in 3.41; the scores in 1.18 to 1.25 at every choice
BLOCK_Q, BLOCK_K = 512, (512, 256, 128)

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def query_block(tile):
    """The queries a grid step of the kernels holds of a tile of
    ``tile``: the whole tile, or 512 of a longer one, a multiple of 16
    (bfloat16 rows pack in pairs of 8); None where the tile admits
    none."""
    block = min(tile, BLOCK_Q)
    return None if block % 16 or tile % block else block


def index_blocks(tile, t):
    """The (queries, keys) a grid step of the kernels holds over a tile
    of ``tile`` queries and ``t`` keys, 512, 256 or 128 keys (the
    scores' lanes); None where no block divides them: the plain form's
    shapes."""
    blocks = (query_block(tile), next((b for b in BLOCK_K if t % b == 0),
                                      None))
    return None if None in blocks else blocks


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=F32)


def _column(block, j):
    """Column ``j`` of ``block`` (rows, n), as (rows, 1)."""
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == j, block, 0.0), axis=-1, keepdims=True)


def _walk(start_ref, tile, block_k):
    """Of this grid step: the position of the tile's last query, of the
    step's first key, and whether any of its keys is at or before that
    query."""
    last = start_ref[0] + tile - 1
    first = pl.program_id(2) * block_k
    return last, first, first <= last


def _keys(k_ref, last, first):
    """This grid step's keys, those after the last query as zeros:
    their products are zero whatever the array holds there."""
    k = k_ref[0]
    at = first + lax.broadcasted_iota(jnp.int32, (k.shape[0], 1), 0)
    return jnp.where(at <= last, k.astype(F32), 0.0).astype(k.dtype)


def _scores_kernel(start_ref, q_ref, k_ref, w_ref, o_ref, *, tile, block_k):
    last, first, live = _walk(start_ref, tile, block_k)

    @pl.when(live)
    def _():
        k, w = _keys(k_ref, last, first), w_ref[0]
        scores = jnp.zeros(o_ref.shape[1:], F32)
        for j in range(q_ref.shape[1]):     # a head's products stay here
            scores = scores + (jnp.maximum(_dot(q_ref[0, j], k, _NT), 0.0)
                               * _column(w, j))
        o_ref[0] = scores

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def _pull_kernel(start_ref, q_ref, k_ref, w_ref, g_ref, to_q_ref, to_k_ref,
                 to_w_ref, to_q_acc, to_w_acc, *, tile, block_k):
    last, first, live = _walk(start_ref, tile, block_k)
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        to_q_acc[...] = jnp.zeros_like(to_q_acc)
        to_w_acc[...] = jnp.zeros_like(to_w_acc)

    @pl.when(live)
    def _():
        k, w, g = _keys(k_ref, last, first), w_ref[0], g_ref[0]
        lane = lax.broadcasted_iota(jnp.int32, w.shape, 1)
        to_k = jnp.zeros(to_k_ref.shape[2:], F32)
        to_w = jnp.zeros(w.shape, F32)
        for j in range(q_ref.shape[1]):     # the products rebuilt a head
            q = q_ref[0, j]
            s = _dot(q, k, _NT)
            passed = s > 0
            ds = jnp.where(passed, g * _column(w, j), 0.0).astype(k.dtype)
            to_q_acc[j] += _dot(ds, k, _NN)
            to_k = to_k + _dot(ds, q, _TN)
            to_w = to_w + jnp.where(lane == j, jnp.sum(
                jnp.where(passed, s * g, 0.0), axis=-1, keepdims=True), 0.0)
        to_k_ref[0, 0] = to_k
        to_w_acc[...] += to_w

    @pl.when(jnp.logical_not(live))
    def _():
        to_k_ref[0, 0] = jnp.zeros_like(to_k_ref[0, 0])

    @pl.when(step == steps - 1)
    def _():
        to_q_ref[0] = to_q_acc[...].astype(to_q_ref.dtype)
        to_w_ref[0] = to_w_acc[...]


def _specs(heads, width, tile, block_q, block_k):
    """Block specs over grid (batch, query block, key block), the
    position of the tile's first query prefetched: a block of queries by
    head, a block of keys, the queries' weights, a block of scores to
    write and one to read. A step whose keys all lie after the tile's
    last query names the last block that does not, so that it fetches
    nothing."""
    def key(kb, start):
        return jnp.minimum(kb, (start[0] + tile - 1) // block_k)

    return (pl.BlockSpec((1, heads, block_q, width),
                         lambda b, qb, kb, start: (b, 0, qb, 0)),
            pl.BlockSpec((1, block_k, width),
                         lambda b, qb, kb, start: (b, key(kb, start), 0)),
            pl.BlockSpec((1, block_q, heads),
                         lambda b, qb, kb, start: (b, qb, 0)),
            pl.BlockSpec((1, block_q, block_k),
                         lambda b, qb, kb, start: (b, qb, kb)),
            pl.BlockSpec((1, block_q, block_k),
                         lambda b, qb, kb, start: (b, qb, key(kb, start))))


def _scores_call(q, k, w, start, blocks, interpret):
    """``q`` by head (batch, heads, tile, width); the scores."""
    (bs, heads, tile, width), t = q.shape, k.shape[1]
    block_q, block_k = blocks
    by_head, keys, weights, scores, _ = _specs(heads, width, tile, *blocks)
    return pl.pallas_call(
        functools.partial(_scores_kernel, tile=tile, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bs, tile // block_q, t // block_k),
            in_specs=[by_head, keys, weights], out_specs=scores),
        out_shape=jax.ShapeDtypeStruct((bs, tile, t), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(start, q, k, w)


def _pull_call(q, k, w, start, g, blocks, interpret):
    """The scores' cotangent ``g`` (batch, tile, T) float32 pulled back:
    to the queries by head (their dtype), to the keys a block of queries
    (batch, query blocks, T, width) float32, to the weights."""
    (bs, heads, tile, width), t = q.shape, k.shape[1]
    block_q, block_k = blocks
    by_head, keys, weights, _, scores = _specs(heads, width, tile, *blocks)
    return pl.pallas_call(
        functools.partial(_pull_kernel, tile=tile, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bs, tile // block_q, t // block_k),
            in_specs=[by_head, keys, weights, scores],
            out_specs=[by_head,
                       pl.BlockSpec((1, 1, block_k, width),
                                    lambda b, qb, kb, start: (b, qb, kb, 0)),
                       weights],
            scratch_shapes=[pltpu.VMEM((heads, block_q, width), F32),
                            pltpu.VMEM((block_q, heads), F32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bs, tile // block_q, t, width), F32),
                   jax.ShapeDtypeStruct(w.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(start, q, k, w, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _index_scores(q_i, k_i, w, start, blocks, interpret):
    return _scores_call(jnp.moveaxis(q_i, 2, 1), k_i, w, start, blocks,
                        interpret)


def _index_scores_fwd(q_i, k_i, w, start, blocks, interpret):
    return (_index_scores(q_i, k_i, w, start, blocks, interpret),
            (q_i, k_i, w, start))


def _pull(q_i, k_i, w, start, to_scores, blocks, interpret):
    to_q, to_k, to_w = _pull_call(jnp.moveaxis(q_i, 2, 1), k_i, w, start,
                                  to_scores, blocks, interpret)
    return (jnp.moveaxis(to_q, 1, 2), jnp.sum(to_k, axis=1).astype(k_i.dtype),
            to_w)


def _index_scores_bwd(blocks, interpret, residuals, to_scores):
    return (*_pull(*residuals, to_scores, blocks, interpret), None)


_index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


def _kernel_operands(q_i, k_i, w, start, interpret):
    """What the kernels' entries take beside the operands, or None
    where the shapes take the plain form: ``w`` in float32, the tile's
    position as a prefetched scalar (T where there is none: every key
    lies at or before it), the blocks, and whether to interpret."""
    blocks = index_blocks(q_i.shape[1], k_i.shape[1])
    if blocks is None:
        return None
    start = k_i.shape[1] if start is None else start
    return (w.astype(F32), jnp.asarray(start, jnp.int32).reshape(1), blocks,
            ops.interpret(interpret))


def index_scores(q_i, k_i, w, start=None, interpret="auto"):
    """``I`` of a tile of queries against the keys: ``q_i`` (batch,
    tile, heads, width), ``k_i`` (batch, T, width), ``w`` (batch, tile,
    heads) float32 -> (batch, tile, T) float32. ``start`` (a traced
    scalar) is the position of the tile's first query: the keys after
    the tile's last query are then neither read nor multiplied, forward
    or backward, and their scores are zeros; a caller masks the keys
    between a query and its tile's last. None: every key is walked.

    Two Pallas kernels under a ``jax.custom_vjp`` where
    :func:`index_blocks` finds the shapes a block (``interpret="auto"``
    compiles them on a TPU and interprets them elsewhere), a head's
    (queries, keys) products in VMEM only: the forward sums them into a
    block of scores; the pullback rebuilds them a block at a time, ``ds_j
    = to_scores w_j (s_j > 0)`` rounded to the operands' dtype before
    its two products, as the flash kernels round theirs, relu, weights
    and every sum float32. :func:`index_scores_plain` elsewhere."""
    rest = _kernel_operands(q_i, k_i, w, start, interpret)
    if rest is None:
        return index_scores_plain(q_i, k_i, w)
    return _index_scores(q_i, k_i, *rest)


def index_scores_pull(q_i, k_i, w, to_scores, start=None, interpret="auto"):
    """The cotangent ``to_scores`` (batch, tile, T) float32 of
    :func:`index_scores` pulled back to ``q_i``, ``k_i`` and ``w``, each
    in its operand's dtype: what ``jax.vjp`` of it gives, called
    outright, so that a caller inside a loop's body needs no ``jax.vjp``
    there (whose transforms a trace would name the kernel after)."""
    rest = _kernel_operands(q_i, k_i, w, start, interpret)
    if rest is None:
        return jax.vjp(index_scores_plain, q_i, k_i, w)[1](to_scores)
    w, start, blocks, interpret = rest
    return _pull(q_i, k_i, w, start, to_scores, blocks, interpret)


def _sortable(x):
    """float32 -> uint32 that sorts as the floats do."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_top(scores, live, count):
    """int8 (.., rows, T): for each row the ``count`` (.., rows) entries
    of ``scores`` largest among the ``live`` ones, equal scores to the
    earlier position. ``count`` is at least 1 and at most the row's live
    entries."""
    keys = jnp.where(live, _sortable(jnp.where(scores == 0, 0.0, scores)),
                     jnp.uint32(0))
    count = count[..., None]

    def bit(i, least):
        """The threshold's bits, the top one first: the largest value at
        or above which the row has ``count`` entries."""
        probe = least | lax.shift_left(jnp.uint32(1),
                                       (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= probe, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= count
        return jnp.where(enough, probe, least)

    least = lax.fori_loop(0, 32, bit, jnp.zeros(count.shape, jnp.uint32))
    above, equal = keys > least, keys == least
    need = count - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    earlier = jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
    return (above | (equal & (earlier <= need))).astype(jnp.int8)


def _rows(x, i, tile, axis):
    return lax.dynamic_slice_in_dim(x, i * tile, tile, axis)


def select_keys(q_i, k_i, w, topk, tile):
    """The selection (batch, T, T) int8 from ``q_i`` (batch, T, heads,
    width), ``k_i`` (batch, T, width) and ``w`` (batch, T, heads), in
    tiles of ``tile`` queries (a divisor of T)."""
    bs, t = k_i.shape[:2]
    at = jnp.arange(t)

    def one(i):
        with jax.named_scope("index_scores"):
            scores = index_scores(_rows(q_i, i, tile, 1), k_i,
                                  _rows(w, i, tile, 1), i * tile)
        with jax.named_scope("index_select"):
            query = i * tile + jnp.arange(tile)
            return select_top(
                scores, at[None, :] <= query[:, None],
                jnp.broadcast_to(jnp.minimum(query + 1, topk), (bs, tile)))

    select = lax.map(one, jnp.arange(t // tile))     # (tiles, bs, tile, T)
    return checkpoint_name(jnp.moveaxis(select, 0, 1).reshape(bs, t, t),
                           RESIDUAL_NAMES[0])


def _alignment(q_i, k_i, w, q, k, lse, select, scale, tile, with_grads):
    """The loss and, ``with_grads``, its gradients to ``q_i`` and ``w``
    (their dtypes, as a tile's pullback leaves them) and to ``k_i`` (the
    tiles' summed in float32), one pass over the tiles."""
    bs, t = k_i.shape[:2]
    heads, kv = q.shape[1], k.shape[1]
    queries = bs * t

    def one(carry, i):
        loss, to_k = carry
        q_t, w_t = _rows(q_i, i, tile, 1), _rows(w, i, tile, 1)
        keep = _rows(select, i, tile, 1) != 0
        with jax.named_scope("index_scores"):
            scores = index_scores(q_t, k_i, w_t, i * tile)
        with jax.named_scope("index_target"):
            # the heads' probabilities over the selected keys, rebuilt
            # as the backward kernels rebuild them: exp(score - lse)
            # a key-value head's group of query heads at a time: the
            # scores of all the heads of a tile do not stand at once
            grouped = jnp.moveaxis(_rows(q, i, tile, 2).reshape(
                bs, kv, heads // kv, tile, q.shape[-1]), 1, 0)
            row_lse = jnp.moveaxis(_rows(lse, i, tile, 2).reshape(
                bs, kv, heads // kv, tile, 1), 1, 0)

            def group(p, at):
                q_g, k_g, lse_g = at
                s = jnp.einsum("bgqd,bsd->bgqs", q_g, k_g,
                               preferred_element_type=F32) * scale
                return p + jnp.sum(jnp.exp(s - lse_g), axis=1), None

            p, _ = lax.scan(group, jnp.zeros(keep.shape, F32),
                            (grouped, jnp.moveaxis(k, 1, 0), row_lse))
            p = p / heads
            p = jnp.where(keep, p, 0.0)
            log_r = jax.nn.log_softmax(
                jnp.where(keep, scores, -jnp.inf), axis=-1)
            loss = loss + jnp.sum(jnp.where(
                p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_r), 0.0))
            if not with_grads:
                return (loss, to_k), None
            to_scores = jnp.where(
                keep, jnp.exp(log_r) * jnp.sum(p, axis=-1, keepdims=True)
                - p, 0.0) / queries
        with jax.named_scope("index_scores"):
            to_q, to_k_t, to_w = index_scores_pull(q_t, k_i, w_t, to_scores,
                                                   i * tile)
        return (loss, to_k + to_k_t.astype(F32)), (to_q, to_w)

    (loss, to_k), per_tile = lax.scan(
        one, (jnp.zeros((), F32), jnp.zeros(k_i.shape, F32)),
        jnp.arange(t // tile))
    if not with_grads:
        return loss / queries, None
    to_q, to_w = (jnp.moveaxis(x, 0, 1).reshape(bs, t, *x.shape[3:])
                  for x in per_tile)
    return loss / queries, (to_q, to_k, to_w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def alignment_loss(q_i, k_i, w, q, k, lse, select, scale, tile):
    """The indexer's alignment loss of one layer, a float32 scalar.
    ``q_i`` (batch, T, index heads, width), ``k_i`` (batch, T, width),
    ``w`` (batch, T, index heads) are the indexer's and take its
    gradient; ``q`` (batch, heads, T, d), ``k`` (batch, kv heads, T, d),
    ``lse`` (batch, heads, T) and ``select`` (batch, T, T) are
    attention's, constants here; ``scale`` multiplies attention's
    scores."""
    return _alignment(q_i, k_i, w, q, k, lse, select, scale, tile, False)[0]


def _alignment_fwd(q_i, k_i, w, q, k, lse, select, scale, tile):
    loss, grads = _alignment(q_i, k_i, w, q, k, lse, select, scale, tile,
                             True)
    grads = tuple(checkpoint_name(g.astype(x.dtype), RESIDUAL_NAMES[1])
                  for g, x in zip(grads, (q_i, k_i, w)))
    return loss, grads


def _alignment_bwd(scale, tile, grads, ct):
    return (*((ct * g.astype(F32)).astype(g.dtype) for g in grads),
            None, None, None, None)


alignment_loss.defvjp(_alignment_fwd, _alignment_bwd)
