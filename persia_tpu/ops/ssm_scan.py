"""Chunked selective state-space scan (the Mamba-2 recurrence) in XLA.

The recurrence, a head at a time, with a state ``S`` of (head_dim,
state) and a scalar decay a step::

    S_t = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

Stepping through positions one by one makes 8192 tiny dependent updates;
the chunked form turns all but one sixty-fourth of that into matrix
products the MXU runs. Positions are cut into chunks of ``chunk``. Inside
a chunk the output is a masked attention-like product,
``y_i += sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j``, with
``cum`` the running sum of ``A dt`` inside the chunk; each chunk's
contribution to the state is one product; only the chunk-to-chunk carry
is sequential (``lax.scan`` over ``T / chunk`` states); and what the
state entering a chunk adds is one more product. The decays are taken as
``exp`` of differences of the running sum, never as a quotient of two
``exp``: ``exp(-cum_j)`` alone overflows float32 inside one chunk at the
published step sizes.

Precision: ``dt``, the decay, the running sums and every ``exp`` are
float32; the four products take ``compute_dtype`` operands and
accumulate in float32. Memory is linear in ``T``: the largest
intermediate is the per-chunk decay matrix, (heads, chunk, chunk) a
chunk. There is no hand-written backward: the function is plain
``jax.numpy`` and the caller chooses what to save (the hybrid sequence
tower recomputes a whole layer under ``nn.remat``), so autodiff's
residuals are linear in ``T`` too.

B and C come in ``groups``; head ``h`` reads group ``h // (heads //
groups)``. A length that is no multiple of ``chunk`` is padded at the
tail with ``dt = 0``, which neither decays nor feeds the state.
"""

import jax.numpy as jnp
from jax import lax


def ssm_scan(x, dt, a, b, c, chunk: int = 128,
             compute_dtype=jnp.bfloat16):
    """``x`` (batch, T, heads, head_dim); ``dt`` (batch, T, heads), after
    its softplus; ``a`` (heads,), negative; ``b``, ``c`` (batch, T,
    groups, state). Returns ``y`` (batch, T, heads, head_dim) float32,
    without the ``D x`` skip."""
    bs, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups
    pad = -t % chunk
    if pad:
        def tail(v):
            return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = tail(x), tail(dt), tail(b), tail(c)
    nc = (t + pad) // chunk
    f32, cd = jnp.float32, compute_dtype
    dt = dt.astype(f32).reshape(bs, nc, chunk, groups, r)
    xg = x.reshape(bs, nc, chunk, groups, r, p)
    bc = b.astype(cd).reshape(bs, nc, chunk, groups, n)
    cc = c.astype(cd).reshape(bs, nc, chunk, groups, n)
    # running sum of log-decay inside each chunk, (bs, nc, g, r, chunk)
    cum = jnp.cumsum(dt * a.astype(f32).reshape(groups, r), axis=2)
    cum_h = jnp.moveaxis(cum, 2, -1)
    dt_h = jnp.moveaxis(dt, 2, -1)

    # inside a chunk: (C_i . B_j) exp(cum_i - cum_j) dt_j over j <= i
    cb = jnp.einsum("zcign,zcjgn->zcgij", cc, bc,
                    preferred_element_type=f32)
    i = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = cum_h[..., :, None] - cum_h[..., None, :]
    decay = jnp.exp(jnp.where(i >= j, seg, -jnp.inf))
    m = cb[:, :, :, None] * decay * dt_h[..., None, :]
    y = jnp.einsum("zcgrij,zcjgrp->zcigrp", m.astype(cd), xg.astype(cd),
                   preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dt    # (bs, nc, chunk, g, r)
    fed = (xg.astype(f32) * to_end[..., None]).astype(cd)
    own = jnp.einsum("zcjgrp,zcjgn->zcgrpn", fed, bc,
                     preferred_element_type=f32)

    # chunk to chunk: the only sequential part, T / chunk steps
    total = jnp.exp(cum[:, :, -1])                 # (bs, nc, g, r)

    def carry(state, step):
        own_c, total_c = step
        return total_c[..., None, None] * state + own_c, state

    _, entering = lax.scan(
        carry, jnp.zeros((bs, groups, r, p, n), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)        # (bs, nc, g, r, p, n)

    # what the state entering a chunk adds at each of its positions
    off = jnp.einsum("zcign,zcgrpn->zcigrp", cc, entering.astype(cd),
                     preferred_element_type=f32)
    y = y + off * jnp.exp(cum)[..., None]
    return y.reshape(bs, nc * chunk, heads, p)[:, :t]

