"""A learned indexer's part of selected attention (the lightning indexer
of the DeepSeek-V3.2-Exp report): the index scores of every causal
(query, key), the top-k selection a query, and the indexer's alignment
loss with its gradient. Plain ``jax.numpy`` by tiles of queries, so that
no ``(heads, T, T)`` array ever stands whole: a tile of ``tile`` queries
holds ``(index heads, tile, T)`` float32 of index scores (268 MB at 16
heads, 512 queries, 8192 keys) and, for the target, the attention
scores of one key-value head's group of query heads at a time.

``index_scores``: ``I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s])``,
one key for all the index heads; products on the operands' dtype with
float32 sums, everything after them float32.

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

F32 = jnp.float32

# the selection, and the alignment loss's gradients to q_i, k_i and w:
# what a ``jax.checkpoint`` policy keeps (``save_only_these_names``) so
# that neither is made twice a step
RESIDUAL_NAMES = ("index_selection", "index_alignment_grads")


def tile_of(t, most):
    """The largest divisor of ``t`` that is at most ``most``."""
    return max(d for d in range(1, min(t, most) + 1) if t % d == 0)


def index_scores(q_i, k_i, w):
    """``I`` of a tile of queries against every key: ``q_i`` (batch,
    tile, heads, width), ``k_i`` (batch, T, width), ``w`` (batch, tile,
    heads) float32 -> (batch, tile, T) float32. Not masked."""
    s = jnp.einsum("bqhd,bkd->bhqk", q_i, k_i, preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(s) * jnp.moveaxis(w, 2, 1)[..., None],
                   axis=1)


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
                                  _rows(w, i, tile, 1))
        with jax.named_scope("index_select"):
            query = i * tile + jnp.arange(tile)
            return select_top(
                scores, at[None, :] <= query[:, None],
                jnp.broadcast_to(jnp.minimum(query + 1, topk), (bs, tile)))

    select = lax.map(one, jnp.arange(t // tile))     # (tiles, bs, tile, T)
    return checkpoint_name(jnp.moveaxis(select, 0, 1).reshape(bs, t, t),
                           RESIDUAL_NAMES[0])


def _alignment(q_i, k_i, w, q, k, lse, select, scale, tile, with_grads):
    """The loss and, ``with_grads``, its gradients to ``q_i``, ``k_i``
    and ``w`` (float32), one pass over the tiles."""
    bs, t = k_i.shape[:2]
    heads, kv = q.shape[1], k.shape[1]
    queries = bs * t

    def one(carry, i):
        loss, to_k = carry
        q_t, w_t = _rows(q_i, i, tile, 1), _rows(w, i, tile, 1)
        keep = _rows(select, i, tile, 1) != 0
        with jax.named_scope("index_scores"):
            if with_grads:
                scores, pull = jax.vjp(index_scores, q_t, k_i, w_t)
            else:
                scores = index_scores(q_t, k_i, w_t)
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
            to_q, to_k_t, to_w = pull(to_scores)
        return (loss, to_k + to_k_t.astype(F32)), (to_q, to_w)

    (loss, to_k), per_tile = lax.scan(
        one, (jnp.zeros((), F32), jnp.zeros(k_i.shape, F32)),
        jnp.arange(t // tile))
    if not with_grads:
        return loss / queries, None
    to_q, to_w = (jnp.moveaxis(x, 0, 1).reshape(bs, t, *x.shape[3:])
                  for x in per_tile)
    return loss / queries, (to_q.astype(F32), to_k, to_w.astype(F32))


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
