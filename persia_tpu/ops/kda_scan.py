"""Chunked Kimi Delta Attention (a gated delta rule whose decay is a
vector over the key's channels) as Pallas kernels with a written-out
backward.

The recurrence, a head at a time, with a state ``S`` of (key width,
value width), a decay ``alpha_t = exp(g_t)`` in (0, 1] a key channel and
a step size ``beta_t`` in (0, 1)::

    S~  = Diag(alpha_t) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T          o_t = S_t^T q_t

which is ``(I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
v_t^T``: the state forgets a channel at a time, and what it still holds
under the key ``k_t`` is corrected towards ``v_t`` instead of added to.

``ops.ssm_scan`` cannot stand in. There the decay is one scalar a head
a step, so it factors out of a chunk's score matrix; here it sits inside
every inner product, ``sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` with
``G`` the running sum of ``g`` inside the chunk. And the correction makes
the values a position writes depend on those written before it in the
chunk: with ``A_ij = beta_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])``
for ``j < i`` and zero elsewhere, what position ``i`` writes is ``w_i =
beta_i (v_i - S_0^T (k_i exp(G_i))) - sum_j A_ij w_j``, a
unit-lower-triangular system a chunk. With ``T = (I + A)^(-1)``::

    W   = T (beta v) - T (beta k exp(G)) S_0          (chunk, value width)
    O   = (q exp(G)) S_0 + P W       P_ij = sum_d q_i[d] k_j[d]
                                            exp(G_i[d] - G_j[d]), j <= i
    S_C = Diag(exp(G_C)) S_0 + (k exp(G_C - G))^T W

**Every decay is ``exp`` of a non-positive difference of running
sums.** At ``A`` 16 and a softplus of order one ``g`` is -20 a step, and
``exp(-G_j)`` alone overflows float32 within five positions, so ``A``
and ``P`` are never the product of a row scaled by ``exp(G_i)`` and a
column scaled by ``exp(-G_j)``. A chunk is cut into blocks of ``BLOCK``
positions. A block on the diagonal takes ``exp(G_i - G_j)`` element by
element, a column ``j`` at a time: a (BLOCK, key width) tile of the
block's rows against row ``j`` (rows above ``j`` masked before the
``exp``), summed over the channels in float32. A block below the
diagonal takes ``exp(G_i - G_n)`` on the row's side and ``exp(G_n -
G_j)`` on the column's, ``n`` the first position of ``i``'s block: both
at most 1, and a matrix product between them.

``T`` is worked out in float32 at the highest matmul precision: the
diagonal blocks of ``I + A`` inverted by forward substitution, a row at
a time, then merged two by two (``[[X, 0], [-Z C X, Z]]`` is the inverse
of ``[[X^-1, 0], [C, Z^-1]]``), which is as stable as substitution is;
the powers of ``A`` a Neumann product would take grow combinatorially
where keys repeat, as an item seen twice makes them.

Precision: gates, running sums, every ``exp`` and ``T`` are float32;
the other products take ``compute_dtype`` operands and accumulate in
float32.

**The kernels.** ``kda_scan`` is a ``jax.custom_vjp`` of two Pallas
calls over a grid of (batch, heads, groups of ``CHUNKS_A_STEP``
chunks), the last axis sequential. ``q``, ``k``, ``v``, ``g`` are read
as the mixer holds them, (batch, T, heads x width): a head is a lane
block, which a block spec's index map picks, so nothing is moved or
regrouped around the calls. A chunk's running sum, score matrices,
inverse and products live in VMEM; the state (held transposed, value
width x key width, so that a chunk's decay scales its lanes) is a
float32 scratch that the sequential axis carries.

**All the chunks of a grid step are worked at once wherever no state is
met.** A column of a diagonal block depends on nothing but its inputs,
but its steps (a row read, a difference, an ``exp``, two products, a
lane sum, a select) depend on each other, and so do the fifteen steps
of a block's substitution: a chunk at a time the chip waits out each
step's latency (the first form of these kernels read 39.5 ms forward a
layer, the loop alone 26 of them: PERF.md §6 "PR 40"). So one pass of
the column loop takes column ``j`` of every block of the grid step
(thirty-two of them, strided rows of a scratch copy), a step of the
substitution is taken in all the blocks together, the running sums are
doubling steps of rolled rows, and every product that meets no state
has a leading axis of chunks. What is left in sequence is the
state's walk: three products a chunk forward, two backward.

* The forward writes ``o`` (float32) and the state that **enters** each
  chunk (``compute_dtype``; (batch, heads, chunks, value width, key
  width): 134 MB a layer at 8192 positions and 32 heads of 128).
* The backward walks the chunks last to first with the state's
  cotangent in scratch. A chunk's matrices and decays are rebuilt in
  VMEM from its inputs and the state that entered it (no carry is run
  again), and its gradients follow by hand: with ``dW = P^T dO + (k
  exp(G_C - G)) dS``, the solve's right-hand sides get ``T^T [dW, -dW
  S_0^T]`` and ``dA = -(T^T dX) X^T`` (``X = T R``, so no product of
  three (chunk, chunk) matrices); a score matrix ``M_ij = sum_d x_i[d]
  k_j[d] exp(G_i[d] - G_j[d])`` gives ``dG_i += x_i dx_i`` and ``dG_j
  -= k_j dk_j``, so the running sum's gradient is read off the rows'
  and columns' own; ``dg`` is its running sum from the chunk's end. A
  pair inside one diagonal block adds to ``dG_i`` what it takes from
  ``dG_j``, so those parts are summed back to their block's end only:
  carried on to the chunk's start their roundings, which do not cancel,
  would lie under every earlier ``dg`` alike, and ``A_log``'s gradient,
  a sum of all of a head's ``dg g``, adds what is alike.

What autodiff keeps is named: ``RESIDUAL_NAMES`` are ``o`` and the
entering states (``jax.ad_checkpoint.checkpoint_name``). Under an
``nn.remat`` whose policy saves them (``models/hybrid_seq``) the rebuilt
layer does not run the forward kernel again; without such a policy the
forward runs a second time, and that is all.

A length that is no multiple of a grid step's positions is padded at
the tail with ``g = 0`` and ``beta = 0``, which neither decays nor
writes. On the CPU the kernels run interpreted (``interpret="auto"``);
the chip's compiler takes head widths that are multiples of 128 lanes
(a narrower head is a block of its own width, which only the
interpreter takes). Times alone on the chip: ``tools/kda_times.py``;
PERF.md §6 "PR 40".
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 16
F32 = jnp.float32
# chunks a grid step: what the kernels work at once (above); and the
# rows of the step-size gradient's block, which the chip wants in eights.
# Sixteen pass the 16 MB of VMEM a call gets by default in the backward
# and read level with eight alone (PERF.md §7)
CHUNKS_A_STEP = 8
# what nn.remat is to keep of the op: its output and the states that
# enter the chunks
RESIDUAL_NAMES = ("kda_scan_out", "kda_scan_states")

_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def kda_gate(a, a_log, dt_bias):
    """The log-decay ``g = -exp(A_log[head]) softplus(a + dt_bias)``,
    float32 and never positive: ``a`` (batch, T, heads, key width) as
    projected, ``a_log`` (heads,), ``dt_bias`` (heads * key width,)."""
    heads, width = a.shape[-2:]
    step = jax.nn.softplus(a.astype(F32) + dt_bias.reshape(heads, width))
    return -jnp.exp(a_log.astype(F32))[:, None] * step


def _dot(a, b, dims, cd=F32):
    """``a`` and ``b`` in ``cd`` (float32: at the highest precision),
    accumulated in float32; with a leading axis of chunks, a chunk at a
    time."""
    if a.ndim == 3:
        (left, right), _ = dims
        dims = ((left[0] + 1,), (right[0] + 1,)), ((0,), (0,))
    return lax.dot_general(
        a.astype(cd), b.astype(cd), dims, preferred_element_type=F32,
        precision=lax.Precision.HIGHEST if cd == F32 else None)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sums(g, chunk, reverse=False):
    """``g`` (rows, width), whole chunks of whole blocks, summed from
    each block's first row (the differences inside a block lose the
    fewest bits so) and from each chunk's, or, ``reverse``, back from
    the blocks' and the chunks' last: two (rows, width) float32. In
    place, by doubling steps of rolled rows."""
    rows, width = g.shape
    within = _iota(g.shape, 0) & (BLOCK - 1)
    local, step = g, 1
    while step < BLOCK:
        local = local + (
            jnp.where(within < BLOCK - step,
                      pltpu.roll(local, rows - step, axis=0), 0.0)
            if reverse else
            jnp.where(within >= step, pltpu.roll(local, step, axis=0), 0.0))
        step *= 2
    blocks = local.reshape(rows // chunk, chunk // BLOCK, BLOCK, width)
    blocks = [blocks[:, b] for b in range(chunk // BLOCK)]
    edge = slice(0, 1) if reverse else slice(BLOCK - 1, BLOCK)
    if reverse:
        blocks.reverse()
    for b in range(1, len(blocks)):
        blocks[b] = blocks[b] + blocks[b - 1][:, edge]
    if reverse:
        blocks.reverse()
    return local, jnp.concatenate(blocks, axis=1).reshape(rows, width)


def _unit_lower_inverse(a):
    """``(I + a)^(-1)`` for ``a`` (chunks, n, n) strictly lower
    triangular, ``n`` a power of two times ``BLOCK``; float32, highest
    precision."""
    chunks, n, _ = a.shape
    nb = n // BLOCK
    # the diagonal blocks, (blocks, BLOCK, BLOCK), and their inverses'
    # transposes ``y`` likewise: column r of a block's is e_r minus the
    # columns before it weighted by the block's row r
    by_block = a.reshape(chunks, nb, BLOCK, n)
    diag = jnp.concatenate([
        by_block[:, b, :, b * BLOCK:(b + 1) * BLOCK] for b in range(nb)],
        axis=1).reshape(chunks * nb, BLOCK, BLOCK)
    lane, within = _iota(diag.shape, 2), _iota(diag.shape, 1)
    y = (lane == within).astype(F32)
    for r in range(1, BLOCK):
        column = (within == r).astype(F32) - jnp.sum(
            diag[:, r:r + 1] * y, axis=-1, keepdims=True)
        y = jnp.where(lane == r, column, y)
    row, col = _iota(a.shape, 1), _iota(a.shape, 2)
    bits = BLOCK.bit_length() - 1       # sizes are powers of two: 2^bits
    inv = jnp.where(
        row >> bits == col >> bits,
        jnp.concatenate([jnp.swapaxes(y, 1, 2).reshape(chunks, n, BLOCK)]
                        * nb, axis=-1), 0.0)
    while 1 << bits < n:
        # pairs of inverted diagonal blocks (x above, z below) and the
        # block c of `a` that lies under x, left of z: -z c x
        under = ((row >> bits) & 1 == 1) & (col >> bits == (row >> bits) - 1)
        inv = inv - _dot(_dot(inv, jnp.where(under, a, 0.0), _NN), inv, _NN)
        bits += 1
    return inv


class _Blocks(NamedTuple):
    """What the score matrices' two passes share, for ``b`` a chunk's
    blocks after its first: the rows' decay to their block's first row,
    twice over (keys, then queries), (chunks, 2 BLOCK, dk); the earlier
    rows' decay from that row, (chunks, b BLOCK, dk)."""
    to_first: list
    from_first: list


def _blocks(local, cum, chunk):
    rows, dk = cum.shape
    nb = chunk // BLOCK
    local = local.reshape(rows // chunk, nb, BLOCK, dk)
    cum = cum.reshape(rows // chunk, chunk, dk)
    to_first, from_first = [None], [None]
    for b in range(1, nb):
        decay = jnp.exp(local[:, b] - local[:, b, :1])
        to_first.append(jnp.concatenate([decay, decay], axis=1))
        from_first.append(jnp.exp(cum[:, b * BLOCK:b * BLOCK + 1]
                                  - cum[:, :b * BLOCK]))
    return _Blocks(to_first, from_first)


def _diagonal_masks(blocks, chunk, dk):
    """For (blocks, BLOCK, ..) arrays of a grid step's blocks: a row's
    place in its block over the key's channels, and a chunk's column
    counted from the block's own first."""
    shape = (blocks, BLOCK, chunk)
    own = (_iota(shape, 0) & (chunk // BLOCK - 1)) * BLOCK
    return _iota((blocks, BLOCK, dk), 1), _iota(shape, 2) - own


def _scores(q, k, local, parts, k_ref, local_ref, chunk, cd):
    """``sum_d x_i[d] k_j[d] exp(G_i[d] - G_j[d])`` over ``j <= i`` of a
    chunk, for ``x`` the keys and the queries: two (chunks, chunk,
    chunk) float32. ``q``, ``k`` and the block-local running sum (rows,
    dk) float32; the two refs hold ``k`` and ``local`` again, for a row
    of every block at a time."""
    rows, dk = k.shape
    chunks, nb, blocks = rows // chunk, chunk // BLOCK, rows // BLOCK
    k3, q3, l3 = (x.reshape(blocks, BLOCK, dk) for x in (k, q, local))
    above, column = _diagonal_masks(blocks, chunk, dk)

    def diagonal(j, strips):
        at = pl.ds(j, blocks, stride=BLOCK)
        held = (jnp.exp(jnp.where(above >= j, l3 - local_ref[at, :][:, None],
                                  -jnp.inf)) * k_ref[at, :][:, None])
        hit = column == j
        return (jnp.where(hit, jnp.sum(k3 * held, -1, keepdims=True),
                          strips[0]),
                jnp.where(hit, jnp.sum(q3 * held, -1, keepdims=True),
                          strips[1]))

    # unrolled: the passes overlap, a fifth of the op's time (PERF.md)
    strips = lax.fori_loop(
        0, BLOCK, diagonal, (jnp.zeros((blocks, BLOCK, chunk), F32),) * 2,
        unroll=True)
    kk, qk = (x.reshape(chunks, nb, BLOCK, chunk) for x in strips)
    kk, qk = ([x[:, b] for b in range(nb)] for x in (kk, qk))
    k4, q4 = (x.reshape(chunks, nb, BLOCK, dk) for x in (k, q))
    k_of = k.reshape(chunks, chunk, dk)
    for b in range(1, nb):
        lo = b * BLOCK
        below = _dot(
            jnp.concatenate([k4[:, b], q4[:, b]], axis=1) * parts.to_first[b],
            k_of[:, :lo] * parts.from_first[b], _NT, cd)
        below = jnp.concatenate(
            [below, jnp.zeros((chunks, 2 * BLOCK, chunk - lo), F32)], axis=-1)
        kk[b], qk[b] = kk[b] + below[:, :BLOCK], qk[b] + below[:, BLOCK:]
    return jnp.concatenate(kk, axis=1), jnp.concatenate(qk, axis=1)


def _scores_backward(q, k, local, parts, dkk, dqk, k_ref, local_ref, dk_ref,
                     chunk, cd):
    """From the cotangents of :func:`_scores`' two matrices (masked to
    where they were used) to ``dq``, ``dk`` and the running sum's
    gradient, ``x_i dx_i`` a row less ``k_j dk_j`` a column, of the
    pairs inside a diagonal block and of those across blocks apart (the
    first add to nothing over a block), each (chunks, chunk, dk)
    float32. ``dk_ref`` is scratch for the columns' side. ``q`` and
    ``k`` as rows or by chunk, as the caller holds them."""
    chunks, dk = dkk.shape[0], k.shape[-1]
    nb, blocks = chunk // BLOCK, chunks * chunk // BLOCK
    k3, q3, l3 = (x.reshape(blocks, BLOCK, dk) for x in (k, q, local))
    dkk3, dqk3 = (x.reshape(blocks, BLOCK, chunk) for x in (dkk, dqk))
    above, column = _diagonal_masks(blocks, chunk, dk)

    def diagonal(j, sides):
        at = pl.ds(j, blocks, stride=BLOCK)
        decay = jnp.exp(jnp.where(above >= j, l3 - local_ref[at, :][:, None],
                                  -jnp.inf))
        hit = column == j
        of_k = jnp.sum(jnp.where(hit, dkk3, 0.0), -1, keepdims=True)
        of_q = jnp.sum(jnp.where(hit, dqk3, 0.0), -1, keepdims=True)
        held = decay * k_ref[at, :][:, None]
        dk_ref[at, :] = jnp.sum((of_k * k3 + of_q * q3) * decay, axis=1)
        return sides[0] + of_k * held, sides[1] + of_q * held

    dk_row, dq = lax.fori_loop(
        0, BLOCK, diagonal, (jnp.zeros((blocks, BLOCK, dk), F32),) * 2,
        unroll=True)
    dk_col = dk_ref[...].reshape(blocks, BLOCK, dk)
    own = (q3 * dq + k3 * (dk_row - dk_col)).reshape(chunks, chunk, dk)
    dk_row, dq = (x.reshape(chunks, nb, BLOCK, dk) for x in (dk_row, dq))
    dk_row, dq = ([x[:, b] for b in range(nb)] for x in (dk_row, dq))
    k4, q4 = (x.reshape(chunks, nb, BLOCK, dk) for x in (k, q))
    dkk4, dqk4 = (x.reshape(chunks, nb, BLOCK, chunk) for x in (dkk, dqk))
    k_of = k.reshape(chunks, chunk, dk)
    rows, cols = [jnp.zeros((chunks, BLOCK, dk), F32)], 0.0
    for b in range(1, nb):
        lo = b * BLOCK
        d = jnp.concatenate([dkk4[:, b, :, :lo], dqk4[:, b, :, :lo]], axis=1)
        mine = (_dot(d, k_of[:, :lo] * parts.from_first[b], _NN, cd)
                * parts.to_first[b])
        dk_row[b], dq[b] = dk_row[b] + mine[:, :BLOCK], dq[b] + mine[:, BLOCK:]
        rows.append(k4[:, b] * mine[:, :BLOCK] + q4[:, b] * mine[:, BLOCK:])
        theirs = parts.from_first[b] * _dot(
            d, jnp.concatenate([k4[:, b], q4[:, b]], axis=1)
            * parts.to_first[b], _TN, cd)
        cols = cols + jnp.concatenate(
            [theirs, jnp.zeros((chunks, chunk - lo, dk), F32)], axis=1)
    return (jnp.concatenate(dq, axis=1),
            jnp.concatenate(dk_row, axis=1) + dk_col.reshape(k_of.shape)
            + cols,
            own, jnp.concatenate(rows, axis=1) - k_of * cols)


class _Chunks(NamedTuple):
    """A grid step's chunks' own, rebuilt from their inputs: all that
    meets no state. Float32; a leading axis of chunks but for the
    block-local running sum, (rows, dk)."""
    local: jax.Array        # G from a block's first row
    parts: _Blocks
    grow: jax.Array         # exp(G), (chunks, chunk, dk)
    to_end: jax.Array       # exp(G_C - G)
    total: jax.Array        # exp(G_C), (chunks, 1, dk)
    kk: jax.Array           # sum_d k_i k_j exp(G_i - G_j), j <= i
    p: jax.Array            # P
    inv: jax.Array          # T
    solved: jax.Array       # T [beta v, beta k exp(G)], (.., dv + dk)


def _chunks(q, k, v, g, beta, k_ref, local_ref, chunk, cd):
    """``q``, ``k``, ``g`` (rows, dk), ``v`` (rows, dv), ``beta`` (rows,
    1), all float32, ``rows`` whole chunks."""
    rows, dk = k.shape
    chunks = rows // chunk
    by_chunk = functools.partial(jnp.reshape, shape=(chunks, chunk, -1))
    local, cum = _running_sums(g, chunk)
    k_ref[...] = k
    local_ref[...] = local
    parts = _blocks(local, cum, chunk)
    kk, p = _scores(q, k, local, parts, k_ref, local_ref, chunk, cd)
    row, col = _iota(kk.shape, 1), _iota(kk.shape, 2)
    inv = _unit_lower_inverse(
        jnp.where(row > col, by_chunk(beta) * kk, 0.0))
    grow = jnp.exp(cum)
    solved = _dot(inv, by_chunk(jnp.concatenate(
        [beta * v, beta * k * grow], axis=1)), _NN, cd)
    cum = by_chunk(cum)
    last = cum[:, chunk - 1:]
    return _Chunks(local, parts, by_chunk(grow), jnp.exp(last - cum),
                   jnp.exp(last), kk, p, inv, solved)


def _column(block, index):
    """Column ``index`` (traced) of ``block`` (rows, n), as (rows, 1)."""
    return jnp.sum(jnp.where(_iota(block.shape, 1) == index, block, 0.0),
                   axis=-1, keepdims=True)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref,
                    state_ref, kf_ref, local_ref, *, chunk, cd):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    q, k, v, g = (ref[0].astype(F32) for ref in (q_ref, k_ref, v_ref, g_ref))
    x = _chunks(q, k, v, g, _column(b_ref[0], pl.program_id(1)), kf_ref,
                local_ref, chunk, cd)
    chunks, dv, _ = s_ref.shape[2:]
    by_chunk = functools.partial(jnp.reshape, shape=(chunks, chunk, -1))
    qg, ke = by_chunk(q) * x.grow, by_chunk(k) * x.to_end
    state = state_ref[...]
    for c in range(chunks):     # the state's walk: three products a chunk
        s_ref[0, 0, c] = state.astype(s_ref.dtype)
        w = x.solved[c, :, :dv] - _dot(x.solved[c, :, dv:], state, _NT, cd)
        o_ref[0, c * chunk:(c + 1) * chunk, :] = (
            _dot(qg[c], state, _NT, cd) + _dot(x.p[c], w, _NN, cd))
        state = x.total[c] * state + _dot(w, ke[c], _TN, cd)
    state_ref[...] = state


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                     dstate_ref, kf_ref, local_ref, dkc_ref, dw_ref, ds_ref,
                     *, chunk, cd):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    q, k, v, g, do = (ref[0].astype(F32)
                      for ref in (q_ref, k_ref, v_ref, g_ref, do_ref))
    beta = _column(b_ref[0], pl.program_id(1))
    x = _chunks(q, k, v, g, beta, kf_ref, local_ref, chunk, cd)
    states = s_ref[0, 0]
    chunks, dv, dk = states.shape
    by_chunk = functools.partial(jnp.reshape, shape=(chunks, chunk, -1))
    q, k, v, do, beta = map(by_chunk, (q, k, v, do, beta))
    qg, kg, ke = q * x.grow, k * x.grow, k * x.to_end
    kc = x.solved[..., dv:]
    w = x.solved[..., :dv] - _dot(kc, states, _NT, cd)

    # the state's walk back: what each chunk wrote gets its cotangent
    # from the read-outs after it, two products a chunk
    dw_own, ds_own = _dot(x.p, do, _TN, cd), _dot(do, qg, _TN, cd)
    dstate = dstate_ref[...]
    for c in reversed(range(chunks)):
        ds_ref[c] = dstate
        dw = dw_own[c] + _dot(ke[c], dstate, _NT, cd)
        dw_ref[c] = dw
        dstate = ds_own[c] + x.total[c] * dstate - _dot(dw, kc[c], _TN, cd)
    dstate_ref[...] = dstate
    dw, dstate = dw_ref[...], ds_ref[...]

    row, col = _iota(x.p.shape, 1), _iota(x.p.shape, 2)
    dp = jnp.where(row >= col, _dot(do, w, _NT, cd), 0.0)
    dqg = _dot(do, states, _NN, cd)
    dke = _dot(w, dstate, _NN, cd)
    dlast = (jnp.sum(dke * ke, axis=1, keepdims=True) + x.total
             * jnp.sum(states.astype(F32) * dstate, axis=1, keepdims=True))

    # the solve: X = T R, R = beta [v, k exp(G)]
    drhs = _dot(x.inv, jnp.concatenate(
        [dw, -_dot(dw, states, _NN, cd)], axis=-1), _TN, cd)
    da = jnp.where(row > col, -_dot(drhs, x.solved, _NT, cd), 0.0)
    dbeta = (jnp.sum(drhs * jnp.concatenate([v, kg], axis=-1), -1,
                     keepdims=True)
             + jnp.sum(da * x.kk, -1, keepdims=True))
    dkg = beta * drhs[..., dv:]

    # the score matrices
    dq, dk, own, across = _scores_backward(
        q, k, x.local, x.parts, beta * da, dp, kf_ref, local_ref, dkc_ref,
        chunk, cd)
    dcum = qg * dqg + kg * dkg - ke * dke + across
    dcum = dcum + jnp.where(_iota(dcum.shape, 1) == chunk - 1, dlast, 0.0)

    def flat(a):
        return a.reshape(chunks * chunk, -1)

    dq_ref[0] = flat(x.grow * dqg + dq).astype(dq_ref.dtype)
    dk_ref[0] = flat(x.grow * dkg + x.to_end * dke + dk).astype(dk_ref.dtype)
    dv_ref[0] = flat(beta * drhs[..., :dv]).astype(dv_ref.dtype)
    dg_ref[0] = (_running_sums(flat(own), chunk, reverse=True)[0]
                 + _running_sums(flat(dcum), chunk, reverse=True)[1])
    db_ref[0, 0] = jnp.swapaxes(dbeta, 1, 2).reshape(chunks, chunk)


def _auto(interpret):
    return jax.default_backend() != "tpu" if interpret == "auto" \
        else interpret


def _steps(t, chunk):
    """Chunks a grid step, and the grid steps that cover ``t``."""
    per = min(CHUNKS_A_STEP, -(-t // chunk))
    return per, -(-t // (per * chunk))


def _specs(heads, dk, dv, chunk, per, steps, reverse):
    """Block specs over the arrays as the mixer holds them, heads and
    width flattened: a head's ``per`` chunks of key width, of value
    width, of the step sizes (all the heads': a head is a lane there),
    and of the entering states; the grid's last axis first to last, or
    ``reverse``."""
    def at(c):
        return steps - 1 - c if reverse else c

    def wide(width):
        return pl.BlockSpec((1, per * chunk, width),
                            lambda b, h, c: (b, at(c), h))

    return (wide(dk), wide(dv),
            pl.BlockSpec((1, per * chunk, heads),
                         lambda b, h, c: (b, at(c), 0)),
            pl.BlockSpec((1, 1, per, dv, dk),
                         lambda b, h, c: (b, h, at(c), 0, 0)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(q, k, v, g, beta, chunk, cd, interpret):
    """Operands flattened and padded: ``q``, ``k``, ``g`` (batch, T,
    heads dk), ``v`` (batch, T, heads dv), ``beta`` (batch, T, heads).
    Returns ``o`` (batch, T, heads dv) float32 and the entering states
    (batch, heads, chunks, dv, dk) in ``cd``."""
    bs, t, heads = beta.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    per, steps = _steps(t, chunk)
    key, value, step, states = _specs(heads, dk, dv, chunk, per, steps, False)
    call = pl.pallas_call(
        functools.partial(_forward_kernel, chunk=chunk, cd=cd),
        grid=(bs, heads, steps),
        in_specs=[key, key, value, key, step],
        out_specs=[value, states],
        out_shape=[jax.ShapeDtypeStruct(v.shape, F32),
                   jax.ShapeDtypeStruct((bs, heads, t // chunk, dv, dk), cd)],
        scratch_shapes=[pltpu.VMEM((dv, dk), F32),
                        pltpu.VMEM((per * chunk, dk), F32),
                        pltpu.VMEM((per * chunk, dk), F32)],
        compiler_params=_PARAMS, interpret=interpret)
    return call(q, k, v, g, beta)


def _backward(q, k, v, g, beta, states, do, chunk, cd, interpret):
    """The five gradients, shaped and typed as :func:`_forward`'s
    operands but the step size's, (batch, heads, chunks, chunk)."""
    bs, t, heads = beta.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    per, steps = _steps(t, chunk)
    key, value, step, entering = _specs(heads, dk, dv, chunk, per, steps, True)
    call = pl.pallas_call(
        functools.partial(_backward_kernel, chunk=chunk, cd=cd),
        grid=(bs, heads, steps),
        in_specs=[key, key, value, key, step, entering, value],
        out_specs=[key, key, value, key,
                   pl.BlockSpec((1, 1, per, chunk),
                                lambda b, h, c: (b, h, steps - 1 - c, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, F32),
                   jax.ShapeDtypeStruct((bs, heads, t // chunk, chunk), F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), F32),
                        pltpu.VMEM((per * chunk, dk), F32),
                        pltpu.VMEM((per * chunk, dk), F32),
                        pltpu.VMEM((per * chunk, dk), F32),
                        pltpu.VMEM((per, chunk, dv), F32),
                        pltpu.VMEM((per, dv, dk), F32)],
        compiler_params=_PARAMS, interpret=interpret)
    return call(q, k, v, g, beta, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, chunk, cd, interpret):
    return _forward(q, k, v, g, beta, chunk, cd, interpret)[0]


def _kda_fwd(q, k, v, g, beta, chunk, cd, interpret):
    o, states = (checkpoint_name(x, name) for x, name in zip(
        _forward(q, k, v, g, beta, chunk, cd, interpret), RESIDUAL_NAMES))
    return o, (q, k, v, g, beta, states)


def _kda_bwd(chunk, cd, interpret, residuals, do):
    *grads, dbeta = _backward(*residuals, do, chunk, cd, interpret)
    bs, heads, _, _ = dbeta.shape
    return (*grads, jnp.swapaxes(dbeta.reshape(bs, heads, -1), 1, 2))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_scan(q, k, v, g, beta, chunk: int = 64,
             compute_dtype=jnp.bfloat16, interpret="auto"):
    """``q``, ``k`` (batch, T, heads, key width), already normed and the
    query scaled; ``v`` (batch, T, heads, value width); ``g`` (batch, T,
    heads, key width), the log-decay, never positive; ``beta`` (batch,
    T, heads). Returns ``o`` (batch, T, heads, value width) float32.
    ``interpret="auto"`` compiles on a TPU and interprets elsewhere."""
    bs, t, heads, _ = q.shape
    if chunk % BLOCK or (chunk // BLOCK) & (chunk // BLOCK - 1):
        raise ValueError(f"chunk {chunk}: a power of two times {BLOCK}")
    per, steps = _steps(t, chunk)

    def flat(x):    # (bs, T, heads, w) -> (bs, T', heads w), tail padded
        x = x.reshape(bs, t, -1)
        return jnp.pad(x, [(0, 0), (0, steps * per * chunk - t), (0, 0)])

    o = _kda(flat(q), flat(k), flat(v), flat(g.astype(F32)),
             flat(beta.astype(F32)), chunk, jnp.dtype(compute_dtype),
             _auto(interpret))
    return o[:, :t].reshape(bs, t, heads, -1)
