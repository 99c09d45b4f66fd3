"""Chunked Kimi Delta Attention (a gated delta rule whose decay is a
vector over the key's channels) in XLA.

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

``T`` and the two products it multiplies are the chunk's own; the three
lines above run chunk after chunk, ``T / chunk`` sequential steps of
matrix products over the (heads, key width, value width) state
(``lax.scan``), and hand each chunk the state that enters it.

**Every decay is ``exp`` of a non-positive difference of running
sums.** At ``A`` 16 and a softplus of order one ``g`` is -20 a step, and
``exp(-G_j)`` alone overflows float32 within five positions, so ``A``
and ``P`` are never the product of a row scaled by ``exp(G_i)`` and a
column scaled by ``exp(-G_j)``. A chunk is cut into blocks of ``BLOCK``
positions. A block on the diagonal takes ``exp(G_i - G_j)`` element by
element (``j <= i``; the rest masked before the ``exp``), a (BLOCK,
BLOCK, key width) tensor a block, which is looped over heads
(``lax.map`` of a ``jax.checkpoint``: for all 32 heads at once it would
be 2.1 GB at 8192 positions, and autodiff would keep it). A block below
the diagonal takes ``exp(G_i - G_n)`` on the row's side and ``exp(G_n -
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
float32. Memory is linear in ``T``. There is no hand-written backward:
the function is plain ``jax.numpy``. The heads are worked ``head_group``
at a time under ``jax.checkpoint`` (``lax.map``), so autodiff keeps the
five inputs and rebuilds a group's intermediates when it reaches it:
some forty float32 arrays of (T, heads, 128), 5.6 GB of temporaries a
layer at 8192 positions and 32 heads when all the heads' were kept at
once, beside 7.2 GB of parameters and Adam's moments.
A length that is no multiple of ``chunk`` is padded at the tail with ``g
= 0`` and ``beta = 0``, which neither decays nor writes.
"""

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 16
F32 = jnp.float32


def kda_gate(a, a_log, dt_bias):
    """The log-decay ``g = -exp(A_log[head]) softplus(a + dt_bias)``,
    float32 and never positive: ``a`` (batch, T, heads, key width) as
    projected, ``a_log`` (heads,), ``dt_bias`` (heads * key width,)."""
    heads, width = a.shape[-2:]
    step = jax.nn.softplus(a.astype(F32) + dt_bias.reshape(heads, width))
    return -jnp.exp(a_log.astype(F32))[:, None] * step


def _diagonal_blocks(q, k, cum):
    """``sum_d x_i[d] k_j[d] exp(G_i[d] - G_j[d])`` over ``j <= i`` inside
    each block, for ``x`` the keys and the queries: two (.., blocks,
    BLOCK, BLOCK) float32 arrays. One head's ``q``, ``k``, ``cum`` (..,
    blocks, BLOCK, width), float32."""
    i = lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK, 1), 0)
    j = lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK, 1), 1)
    seg = cum[..., :, None, :] - cum[..., None, :, :]
    held = jnp.exp(jnp.where(i >= j, seg, -jnp.inf)) * k[..., None, :, :]
    return (jnp.sum(k[..., :, None, :] * held, axis=-1),
            jnp.sum(q[..., :, None, :] * held, axis=-1))


def _unit_lower_inverse(a):
    """``(I + a)^(-1)`` for ``a`` (.., n, n) strictly lower triangular,
    ``n`` a power of two times ``BLOCK``; float32, highest precision."""
    n = a.shape[-1]
    nb = n // BLOCK
    blocks = a.reshape(*a.shape[:-2], nb, BLOCK, nb, BLOCK)
    diag = jnp.stack([blocks[..., b, :, b, :] for b in range(nb)], axis=-3)
    # forward substitution: row i of the inverse is e_i minus row i of
    # the block times the rows above it
    eye = jnp.eye(BLOCK, dtype=F32)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (BLOCK,))]
    for r in range(1, BLOCK):
        above = jnp.stack(rows, axis=-2)                # (.., r, BLOCK)
        rows.append(eye[r] - jnp.sum(
            diag[..., r, :r, None] * above, axis=-2))
    inv = jnp.stack(rows, axis=-2)                      # (.., nb, B, B)
    size = BLOCK
    while size < n:
        # pairs of inverted diagonal blocks (x above, z below) and the
        # block c of `a` that lies under x, left of z
        pairs = inv.reshape(*inv.shape[:-3], -1, 2, size, size)
        x, z = pairs[..., 0, :, :], pairs[..., 1, :, :]
        grid = a.reshape(*a.shape[:-2], n // size, size, n // size, size)
        c = jnp.stack([grid[..., 2 * p + 1, :, 2 * p, :]
                       for p in range(n // size // 2)], axis=-3)
        low = -jnp.einsum("...ij,...jk,...kl->...il", z, c, x,
                          precision=lax.Precision.HIGHEST)
        inv = jnp.concatenate(
            [jnp.concatenate([x, jnp.zeros_like(x)], axis=-1),
             jnp.concatenate([low, z], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _some_heads(q, k, v, g, beta, chunk, cd):
    """``kda_scan`` for some of the heads, the length already a whole
    number of chunks: ``q``, ``k``, ``g`` (heads, batch, T, key width),
    ``v`` (heads, batch, T, value width), ``beta`` (heads, batch, T).
    Returns ``o`` (heads, batch, T, value width) float32."""
    heads, bs, t, dk = q.shape
    dv = v.shape[-1]
    nc, nb = t // chunk, chunk // BLOCK

    def chunks(x):      # (heads, bs, T, w) -> (heads, bs, nc, chunk, w)
        return x.reshape(heads, bs, nc, chunk, -1)

    q, k, v = chunks(q.astype(F32)), chunks(k.astype(F32)), chunks(v)
    beta = chunks(beta.astype(F32)[..., None])
    cum = jnp.cumsum(chunks(g.astype(F32)), axis=3)     # G, inside a chunk

    # A's and P's blocks on the diagonal, a head at a time
    def blocks(x):
        return x.reshape(bs, nc, nb, BLOCK, -1)

    kk, qk = lax.map(jax.checkpoint(
        lambda x: _diagonal_blocks(*map(blocks, x))), (q, k, cum))
    strips = []     # A's and P's rows, a block of them at a time
    for b in range(nb):
        rows = slice(b * BLOCK, (b + 1) * BLOCK)
        parts = [jnp.stack([kk[..., b, :, :], qk[..., b, :, :]]),
                 jnp.zeros((2, heads, bs, nc, BLOCK, chunk - rows.stop),
                           F32)]
        if b:
            first = cum[..., rows.start:rows.start + 1, :]
            to_first = jnp.exp(cum[..., rows, :] - first)
            before = (k[..., :rows.start, :]
                      * jnp.exp(first - cum[..., :rows.start, :]))
            parts.insert(0, jnp.einsum(
                "xhzcid,hzcjd->xhzcij",
                (jnp.stack([k[..., rows, :], q[..., rows, :]])
                 * to_first).astype(cd),
                before.astype(cd), preferred_element_type=F32))
        strips.append(jnp.concatenate(parts, axis=-1))
    a, p = jnp.concatenate(strips, axis=-2)
    strictly_lower = (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
                      > lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    inv = _unit_lower_inverse(jnp.where(strictly_lower, beta * a, 0.0))

    # the chunk's own: corrected values and keys, the sides that meet
    # the state
    grow = jnp.exp(cum)
    solved = jnp.einsum(
        "hzcij,hzcjw->hzciw", inv.astype(cd),
        jnp.concatenate([beta * v.astype(F32), beta * k * grow],
                        axis=-1).astype(cd), preferred_element_type=F32)
    u, kc = solved[..., :dv], solved[..., dv:].astype(cd)
    to_end = (k * jnp.exp(cum[..., -1:, :] - cum)).astype(cd)
    total = jnp.exp(cum[..., -1, :])                    # (h, bs, nc, dk)

    # chunk to chunk: the only sequential part, T / chunk steps
    def carry(state, step):
        u_c, kc_c, to_end_c, total_c = step
        w = u_c - jnp.einsum("hzik,hzkv->hziv", kc_c, state.astype(cd),
                             preferred_element_type=F32)
        new = total_c[..., None] * state + jnp.einsum(
            "hzik,hziv->hzkv", to_end_c, w.astype(cd),
            preferred_element_type=F32)
        return new, state

    _, entering = lax.scan(
        carry, jnp.zeros((heads, bs, dk, dv), F32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (u, kc, to_end, total)))
    entering = jnp.moveaxis(entering, 0, 2).astype(cd)  # (h, bs, nc, dk, dv)

    # what each position writes, and reads back
    w = u - jnp.einsum("hzcik,hzckv->hzciv", kc, entering,
                       preferred_element_type=F32)
    o = (jnp.einsum("hzcik,hzckv->hzciv", (q * grow).astype(cd), entering,
                    preferred_element_type=F32)
         + jnp.einsum("hzcij,hzcjv->hzciv", p.astype(cd), w.astype(cd),
                      preferred_element_type=F32))
    return o.reshape(heads, bs, t, dv)


def kda_scan(q, k, v, g, beta, chunk: int = 64,
             compute_dtype=jnp.bfloat16, head_group: int = 8):
    """``q``, ``k`` (batch, T, heads, key width), already normed and the
    query scaled; ``v`` (batch, T, heads, value width); ``g`` (batch, T,
    heads, key width), the log-decay, never positive; ``beta`` (batch,
    T, heads). Returns ``o`` (batch, T, heads, value width) float32.
    The heads are worked ``head_group`` at a time (all at once where
    that does not divide them), each group under ``jax.checkpoint``."""
    bs, t, heads, dk = q.shape
    if chunk % BLOCK or (chunk // BLOCK) & (chunk // BLOCK - 1):
        raise ValueError(f"chunk {chunk}: a power of two times {BLOCK}")
    if heads % head_group:
        head_group = heads
    pad = -t % chunk

    def groups(x):  # (bs, T, heads, ..) -> (groups, head_group, bs, T', ..)
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 0)
        return x.reshape(heads // head_group, head_group, *x.shape[1:])

    o = lax.map(
        jax.checkpoint(lambda x: _some_heads(*x, chunk, compute_dtype)),
        tuple(groups(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(heads, bs, t + pad, -1), 0, 2)[:, :t]
