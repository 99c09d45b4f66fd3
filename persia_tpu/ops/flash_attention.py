"""Pallas flash-attention forward for TPU.

Why a kernel here when the embedding-bag measurement said "let XLA do
it": the XLA formulation of blockwise attention
(`parallel/ring_attention.py local_flash_attention`) is a `lax.scan`
whose carry — o/m/l running statistics, (B,H,T,dh)+2×(B,H,T) f32 —
round-trips through HBM on EVERY k/v chunk. At B=4 H=8 T=8192 dh=128
that is ~134 MB of carry read+written per chunk step, ~16× per call:
the op is carry-bandwidth-bound, not MXU-bound. The fix is structural,
not fusion-level, so XLA cannot do it: keep the per-q-block statistics
in VMEM across the k-grid and only write the finished output block.
This is the classic flash-attention schedule mapped onto the Pallas
TPU grid (sequential iteration, innermost axis fastest; scratch
persists across grid steps — see /opt/skills/guides/pallas_guide.md).

What a call walks: not the dense ``(n_q, n_k)`` rectangle of block
pairs but a list of them, ``block_schedule``, made from the call's
static shapes and holding only the pairs in which some score is live.
The three kernels take the list as scalar-prefetch operands, their grid
is ``(b*h, len(pairs))`` and every index map reads its block index from
the list, so a causal call takes no grid step, fetches no block and
builds no mask above the diagonal: at T 8192 136 pairs a head where the
rectangle has 256 in 512 x 512 blocks, 36 of 64 in the 1024 x 1024 that
``_blocks`` picks, and only the 16 (8) that straddle the diagonal pay
for the mask (``interior`` pairs run the same body without the iotas,
the comparisons and the select). A non-causal call gets its whole
rectangle from the same function. ``flash_attention_selected`` walks
the causal list too and hands each kernel, beside the positional mask,
the block of an int8 ``(B, T_q, T_k)`` selection that its scheduled pair
names (one selection for all the heads): a pair is walked whether or not
any of its keys is selected.

Kernel shape rules: the head width is the lane axis of every block, and
there are two of them: queries, keys and their gradients are ``dk``
wide, values, the output and its cotangent ``dv`` (scores contract
``dk``, the output emits ``dv``; neither is padded to the other in HBM).
A width is a full-axis block, padded to whole lanes in VMEM: 64, 128,
256 and 192 beside 128 (a latent-attention head of 128 + 64 rotary key
features over values of 128) all lower. The softmax scale is the
caller's, ``1 / sqrt(dk)`` where it gives none (a rotary scaling rule
brings its own factor). Block sizes are multiples of the
128-lane width (``_clamp_block``) so every tile — bf16 (16, 128) included — and every per-row lane vector
(lse, delta, kv_mask ride as ``(.., 1, T)`` rows blocked ``(1, block)``)
meets the TPU lowering's (8, 128) block rule. T is padded to the k/q
block size by the wrapper; padded KEY positions are masked via the
static true-length (their pairs are ``edge`` pairs), padded QUERY rows
compute garbage that the wrapper slices off.

Backward: Pallas too (jax.custom_vjp). The forward saves (q, k, v,
out, lse); `flash_attention_bwd_pallas` recomputes each softmax block
in VMEM from those residuals with the same schedule run twice — dq
accumulates along a row of pairs, dk/dv along a column. delta
(rowsum(dO·O)) is a cheap XLA reduce. Memory stays O(T) end to end.

Of the five residuals two carry a name, ``RESIDUAL_NAMES``: ``out`` and
``lse``, the two that only the forward kernel can make (q, k and v come
back from their projections; these come back from the one expensive
call). Outside a ``jax.checkpoint`` a name is the identity. Inside one
whose policy is ``save_only_these_names(*RESIDUAL_NAMES)`` (the
sequence tower's ``nn.remat``) both are kept from the forward pass, the
recomputation's forward call has no reader left and is gone from the
step: three calls an attention layer a step (forward with ``lse``, dq,
dk/dv), not four. The named ``out`` is the primal result too, so what
reads the layer's output in the recomputation reads the kept array.

The carry-bandwidth figures above are computed from shapes. Kernel
times and roofline shares on the chip: PERF.md §5, §6 "PR 34", "PR 36".
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from persia_tpu import metrics

_NEG_INF = -1e30  # large-finite: -inf NaNs the m-update on all-masked rows
_LANES = 128

# flags of a scheduled pair
EDGE = 1    # some score of the block is masked: build the positional mask
FIRST = 2   # first pair of its accumulation row: zero the accumulators
LAST = 4    # last pair of its accumulation row: write the output block

# the names of the two residuals only the forward kernel can make, for a
# ``jax.checkpoint`` policy to keep (``save_only_these_names``)
RESIDUAL_NAMES = ("flash_attention_out", "flash_attention_lse")


def block_schedule(t_q: int, t_k: int, block_q: int, block_k: int,
                   causal: bool, by_key: bool = False):
    """The block pairs one call walks, from its static shapes alone:
    ``(qi, ki, flags)``, three int32 arrays of one length.

    A pair is listed exactly when some score of it is live (query and
    key inside their true lengths and, in a causal call, key not after
    query). It is an ``EDGE`` pair exactly when some score of it is
    masked: it straddles the diagonal, or is the last key or query
    block of a padded T. The order is the accumulation order: rows of
    ``qi`` with ``ki`` ascending (forward, dq) or, ``by_key``, columns
    of ``ki`` with ``qi`` ascending (dk/dv); ``FIRST`` and ``LAST``
    mark the ends of each row. A row with nothing live (a causal key
    block no query reaches, t_q < t_k) keeps one fully masked pair, so
    its output block is still written, as zeros."""
    n_q, n_k = -(-t_q // block_q), -(-t_k // block_k)
    qi, ki = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    q_lo, k_lo = qi * block_q, ki * block_k
    q_hi = np.minimum(q_lo + block_q, t_q) - 1   # last true position
    k_hi = np.minimum(k_lo + block_k, t_k) - 1
    live = np.full((n_q, n_k), True)
    edge = (q_lo + block_q > t_q) | (k_lo + block_k > t_k)
    if causal:
        live = q_hi >= k_lo
        edge |= q_lo < k_hi
    if by_key:
        qi, ki, live, edge = qi.T, ki.T, live.T, edge.T
    # dead rows keep the pair nearest the diagonal (masked: EDGE holds)
    dead = ~live.any(axis=1)
    live[dead, -1 if by_key else 0] = True
    row = (ki if by_key else qi)[live]
    ends = np.flatnonzero(np.diff(row)) + 1
    flags = np.where(edge[live], EDGE, 0)
    flags[np.r_[0, ends]] |= FIRST
    flags[np.r_[ends - 1, row.size - 1]] |= LAST
    return tuple(np.asarray(a, np.int32) for a in (qi[live], ki[live], flags))


def _pair(qi_ref, ki_ref, flags_ref):
    """This grid step's pair, read from the prefetched schedule."""
    s = pl.program_id(1)
    return qi_ref[s], ki_ref[s], flags_ref[s]


def _run_bodies(body, flags, has_edge: bool, has_interior: bool):
    """``body(edge)`` under the pair's kind; a schedule of one kind
    only (a non-causal call on unpadded T) compiles one body."""
    if has_edge and has_interior:
        pl.when(flags & EDGE != 0)(lambda: body(True))
        pl.when(flags & EDGE == 0)(lambda: body(False))
    else:
        body(has_edge)


def _block_mask(edge: bool, qi, ki, block_q, block_k, t_k_real, causal,
                mask_row, t_q_real=None, selected=None):
    """The (block_q, block_k) mask of a pair, or None where every score
    is live: positions (padding, causal) only in an edge pair, the
    ``kv_mask`` row, (1, bk) broadcast, and the ``select`` block, one
    entry a (query, key), in either kind."""
    mask = None
    if edge:
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < t_k_real            # padded keys never attend
        if causal or t_q_real is not None:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
        if t_q_real is not None:
            mask = jnp.logical_and(q_pos < t_q_real, mask)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
    if mask_row is not None:
        keep = mask_row > 0
        mask = keep if mask is None else jnp.logical_and(mask, keep)
    if selected is not None:
        keep = selected.astype(jnp.int32) != 0
        mask = keep if mask is None else jnp.logical_and(mask, keep)
    return mask


def _fwd_kernel(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, *rest,
                scale: float, causal: bool, block_q: int, block_k: int,
                t_k_real: int, has_edge: bool, has_interior: bool,
                with_lse: bool, with_mask: bool, with_select: bool = False):
    if with_mask:
        mask_ref, rest = rest[0], rest[1:]
    if with_select:
        select_ref, rest = rest[0], rest[1:]
    o_ref, rest = rest[0], rest[1:]
    if with_lse:
        lse_ref, acc, m_scr, l_scr = rest
    else:
        acc, m_scr, l_scr = rest
    qi, ki, flags = _pair(qi_ref, ki_ref, flags_ref)

    @pl.when(flags & FIRST != 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def _body(edge: bool):
        q = q_ref[0]                       # (block_q, dk) bf16/f32
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        mask = _block_mask(edge, qi, ki, block_q, block_k, t_k_real, causal,
                           mask_ref[...] if with_mask else None,
                           selected=select_ref[...] if with_select else None)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                # (block_q, 128) lane-replicated
        m_cur = jnp.max(s, axis=1, keepdims=True)       # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)              # (bq, 128)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                   # (bq, bk) f32
        if with_mask or with_select:
            # a FULLY-masked row has s = m_new = NEG_INF everywhere, so
            # the subtraction above degenerates to exp(0)=1; zero it
            # (l stays 0 -> output 0, matching reference_attention)
            p = jnp.where(m_new[:, :1] > _NEG_INF / 2, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(
            p, axis=1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, dv)
        acc[...] = acc[...] * alpha[:, :1] + pv

    _run_bodies(_body, flags, has_edge, has_interior)

    @pl.when(flags & LAST != 0)
    def _finish():
        l = jnp.maximum(l_scr[...][:, :1], 1e-20)
        o_ref[0] = (acc[...] / l).astype(o_ref.dtype)
        if with_lse:
            # logsumexp residual for the backward kernels, stored
            # (BH, 1, T) with T on lanes — a (T, 1) layout would be
            # padded to 128 lanes on TPU, 128x the footprint
            lse_ref[...] = jnp.transpose(m_scr[...][:, :1] + jnp.log(l))


def _clamp_block(block, t):
    """Clamp a requested block size to the sequence length, rounded up
    to a multiple of the 128-lane width (e.g. t=100 → block 128, with
    ``_pad_t`` padding T to 128; t=1000, block 512 → T padded to 1024).
    The TPU lowering needs the lane-row blocks ``(1, block)`` divisible
    by 128 and bf16 tiles sublane-aligned to 16; interpret mode accepts
    anything, which is how an 8-aligned clamp once passed every CPU
    test and still could not lower."""
    return -(-min(block, max(t, 1)) // _LANES) * _LANES


def _blocks(block_q, block_k, t_q, t_k, width, dtype):
    """The call's block sizes at head width ``width`` (the wider of the
    key and value widths): a caller's request, or where it makes
    none the size the kernels alone ran fastest at on a v5e (PERF.md §6,
    "PR 34": T 2048-8192, head widths 64-256, bfloat16). 1024 rows a
    block: at T 8192 the three calls take a third less time than in 512s
    at head width 128 and 8 % less at 256, since what a step does beside
    its products (row statistics, rescaling, the pipeline's turn) is
    spread over four times the scores. 512 where a 1024-row tile of the
    inputs would pass 512 KiB (float32 at width 256), which does not fit
    VMEM beside the float32 score blocks. Either is clamped to T."""
    fits = 1024 * width * jnp.dtype(dtype).itemsize <= 512 * 1024
    default = 1024 if fits else 512
    return (_clamp_block(block_q or default, t_q),
            _clamp_block(block_k or default, t_k))


def _pad_t(x, block, axis=1):
    """Zero-pad ``axis`` up to a multiple of ``block``."""
    pad = (-x.shape[axis]) % block
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


def _scale(scale, dk):
    """The scores' factor: the caller's, or ``1 / sqrt(dk)``."""
    return 1.0 / float(dk) ** 0.5 if scale is None else float(scale)


def _mask_rows(kv_mask, block_k):
    """(B, T_k) key-validity mask -> (B, 1, T_k padded) f32 0/1 rows."""
    return _pad_t(kv_mask.astype(jnp.float32), block_k)[:, None, :]


def _spec_family(block_q, block_k, dk, dv, h):
    """The six block-spec shapes every kernel here uses: q-tile and
    k-tile at the key width (q, k, dq, dk), v-tile and o-tile at the
    value width (v, dv by key block; out, do by query block), per-q lane
    row (lse/delta, arrays shaped (BH, 1, T)), per-k lane row (kv_mask,
    (B, 1, T), batch axis = bh // h). The row arrays
    carry a unit sublane axis so the block's last two dims are
    (1 == full, block % 128 == 0) — a bare ``(1, block)`` block over
    ``(BH, T)`` does not lower for TPU. The grid is (bh, step) and a
    step's block indices are the schedule's: ``qi[s]`` and ``ki[s]``,
    whatever order the schedule walks them in. One definition so a
    layout change cannot drift between the forward and the two backward
    calls."""
    def by_query(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda bh, s, qi, ki, flags: (bh, qi[s], 0))

    def by_key(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda bh, s, qi, ki, flags: (bh, ki[s], 0))

    return (
        by_query(dk), by_key(dk), by_key(dv), by_query(dv),
        pl.BlockSpec((None, 1, block_q),
                     lambda bh, s, qi, ki, flags: (bh, 0, qi[s])),
        pl.BlockSpec((None, 1, block_k),
                     lambda bh, s, qi, ki, flags, h=h: (bh // h, 0, ki[s])),
    )


def _select_operand(select, block_q, block_k, h):
    """A (B, T_q, T_k) int8 selection, one entry a (query, key) and
    shared by the ``h`` heads, padded to whole blocks with zeros (a
    padded pair attends nothing), and the spec that hands a kernel the
    block of its scheduled pair."""
    spec = pl.BlockSpec(
        (None, block_q, block_k),
        lambda bh, s, qi, ki, flags, h=h: (bh // h, qi[s], ki[s]))
    return spec, _pad_t(_pad_t(select.astype(jnp.int8), block_q, axis=1),
                        block_k, axis=2)


def _scheduled_call(kernel, schedule, bh, in_specs, out_specs, out_shape,
                    scratch_shapes, interpret, operands):
    """One kernel over grid (bh, pairs) of ``block_schedule(*schedule)``,
    the pairs prefetched as scalars and the kernel told which of its two
    bodies the schedule needs at all. Sets the process's gauges of how
    far the schedule cut the rectangle (a head, of the call built last):
    pairs walked, pairs a dense grid would have, edge pairs."""
    pairs = block_schedule(*schedule)
    t_q, t_k, block_q, block_k = schedule[:4]
    n_edge = int(np.count_nonzero(pairs[2] & EDGE))
    for name, n in (("walked", pairs[0].size),
                    ("dense", -(-t_q // block_q) * -(-t_k // block_k)),
                    ("edge", n_edge)):
        metrics.default_registry().gauge(
            f"flash_attention_pairs_{name}").set(n)
    return pl.pallas_call(
        functools.partial(kernel, has_edge=n_edge > 0,
                          has_interior=n_edge < pairs[0].size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, pairs[0].size),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        interpret=interpret,
    )(*pairs, *operands)


def flash_attention_fwd_pallas(q, k, v, causal: bool = False,
                               block_q=None, block_k=None,
                               interpret: bool = False,
                               return_lse: bool = False,
                               kv_mask=None, scale=None, select=None):
    """Forward Pallas flash attention. q/k: (B, H, T, Dk), v and the
    output (B, H, T, Dv); ``scale`` multiplies the scores, ``1 /
    sqrt(Dk)`` where it is None.

    With ``return_lse`` also returns the (B, H, T) logsumexp residual
    the backward kernels consume. ``kv_mask`` optional (B, T_k) of
    valid key positions, ``select`` optional (B, T_q, T_k) int8 of the
    keys each query attends (nonzero), shared by the heads;
    fully-masked query rows yield 0."""
    b, h, t_q, dk = q.shape
    t_k, dv = k.shape[2], v.shape[3]
    block_q, block_k = _blocks(block_q, block_k, t_q, t_k, max(dk, dv),
                               q.dtype)
    qp = _pad_t(q.reshape(b * h, t_q, dk), block_q)
    kp = _pad_t(k.reshape(b * h, t_k, dk), block_k)
    vp = _pad_t(v.reshape(b * h, t_k, dv), block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=_scale(scale, dk), causal=causal,
        block_q=block_q, block_k=block_k, t_k_real=t_k,
        with_lse=return_lse, with_mask=kv_mask is not None,
        with_select=select is not None)
    q_spec, k_spec, v_spec, o_spec, qrow_spec, krow_spec = _spec_family(
        block_q, block_k, dk, dv, h)
    in_specs = [q_spec, k_spec, v_spec]
    operands = [qp, kp, vp]
    if kv_mask is not None:
        # (B, T_k) f32 0/1; the grid's bh axis maps back to batch bh//h
        in_specs.append(krow_spec)
        operands.append(_mask_rows(kv_mask, block_k))
    if select is not None:
        for one, into in zip(_select_operand(select, block_q, block_k, h),
                             (in_specs, operands)):
            into.append(one)
    o_shape = jax.ShapeDtypeStruct((*qp.shape[:2], dv), q.dtype)
    if return_lse:
        out_specs = (o_spec, qrow_spec)
        out_shape = (o_shape, jax.ShapeDtypeStruct(
            (b * h, 1, qp.shape[1]), jnp.float32))
    else:  # serving path: no lse output, no wasted HBM write
        out_specs, out_shape = o_spec, o_shape
    res = _scheduled_call(
        kernel, (t_q, t_k, block_q, block_k, causal), b * h, in_specs,
        out_specs, out_shape,
        [pltpu.VMEM((block_q, dv), jnp.float32),
         pltpu.VMEM((block_q, _LANES), jnp.float32),
         pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret, operands)
    if return_lse:
        out, lse = res
        return (out[:, :t_q].reshape(b, h, t_q, dv),
                lse[:, 0, :t_q].reshape(b, h, t_q))
    return res[:, :t_q].reshape(b, h, t_q, dv)


def _masked_p(q, k, lse, mask, *, scale):
    """Recompute the (block_q, block_k) softmax block from q/k/lse under
    the pair's mask (``_block_mask``; None in an interior pair) — shared
    by both backward kernels. Fully-masked rows (lse pinned at NEG_INF
    by the forward) are forced to p=0, not the exp(0)=1 the raw
    arithmetic gives."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)
    return jnp.where(lse > _NEG_INF / 2, p, 0.0)


def _mask_operands(rest, with_mask: bool, with_select: bool):
    """The ``kv_mask`` row's and the ``select`` block's refs (None where
    the call has none) off the front of a backward kernel's trailing
    operands, and what is left."""
    mask_ref = select_ref = None
    if with_mask:
        mask_ref, rest = rest[0], rest[1:]
    if with_select:
        select_ref, rest = rest[0], rest[1:]
    return mask_ref, select_ref, rest


def _bwd_dq_kernel(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, *rest, scale: float, causal: bool,
                   block_q: int, block_k: int, t_q_real: int,
                   t_k_real: int, has_edge: bool, has_interior: bool,
                   with_mask: bool, with_select: bool = False):
    mask_ref, select_ref, (dq_ref, dq_acc) = _mask_operands(
        rest, with_mask, with_select)
    qi, ki, flags = _pair(qi_ref, ki_ref, flags_ref)

    @pl.when(flags & FIRST != 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body(edge: bool):
        # lse/delta ride in (1, block_q) lane-major rows (a (T, 1)
        # layout would be 128-lane padded in HBM); transpose to columns
        lse = jnp.transpose(lse_ref[...])               # (bq, 1)
        delta = jnp.transpose(delta_ref[...])
        mask = _block_mask(edge, qi, ki, block_q, block_k, t_k_real, causal,
                           None if mask_ref is None else mask_ref[...],
                           t_q_real,
                           None if select_ref is None else select_ref[...])
        p = _masked_p(q_ref[0], k_ref[0], lse, mask, scale=scale)
        do = do_ref[0]
        dp = jax.lax.dot_general(                       # dO @ V^T
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                           # (bq, bk)
        dq_acc[...] += jax.lax.dot_general(             # ds @ K
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    _run_bodies(_body, flags, has_edge, has_interior)

    @pl.when(flags & LAST != 0)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, *rest, scale: float,
                    causal: bool, block_q: int, block_k: int,
                    t_q_real: int, t_k_real: int, has_edge: bool,
                    has_interior: bool, with_mask: bool,
                    with_select: bool = False):
    mask_ref, select_ref, (dk_ref, dv_ref, dk_acc, dv_acc) = _mask_operands(
        rest, with_mask, with_select)
    qi, ki, flags = _pair(qi_ref, ki_ref, flags_ref)

    @pl.when(flags & FIRST != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body(edge: bool):
        q = q_ref[0]
        lse = jnp.transpose(lse_ref[...])               # (bq, 1)
        delta = jnp.transpose(delta_ref[...])
        mask = _block_mask(edge, qi, ki, block_q, block_k, t_k_real, causal,
                           None if mask_ref is None else mask_ref[...],
                           t_q_real,
                           None if select_ref is None else select_ref[...])
        p = _masked_p(q, k_ref[0], lse, mask, scale=scale)
        do = do_ref[0]
        dv_acc[...] += jax.lax.dot_general(             # P^T @ dO
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(                       # dO @ V^T
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[...] += jax.lax.dot_general(             # ds^T @ Q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    _run_bodies(_body, flags, has_edge, has_interior)

    @pl.when(flags & LAST != 0)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, out, lse, do, causal: bool = False,
                               block_q=None, block_k=None,
                               interpret: bool = False, kv_mask=None,
                               scale=None, select=None):
    """Pallas flash-attention backward: (dq, dk, dv), the first two at
    the key width, the third at the value width; ``kv_mask`` and
    ``select`` as the forward call had them.

    Same schedule as the forward, run twice: dq revisits its q-block
    accumulator along a row of pairs; dk/dv revisit their k-block
    accumulators along a column (``by_key``). The softmax block is
    recomputed from (q, k, lse) in VMEM — nothing quadratic ever
    touches HBM.
    """
    b, h, t_q, dk = q.shape
    t_k, dv = k.shape[2], v.shape[3]
    block_q, block_k = _blocks(block_q, block_k, t_q, t_k, max(dk, dv),
                               q.dtype)
    # delta_i = rowsum(dO_i * O_i) — cheap XLA elementwise+reduce
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                             # (b, h, t_q)
    qp = _pad_t(q.reshape(b * h, t_q, dk), block_q)
    kp = _pad_t(k.reshape(b * h, t_k, dk), block_k)
    vp = _pad_t(v.reshape(b * h, t_k, dv), block_k)
    dop = _pad_t(do.reshape(b * h, t_q, dv), block_q)
    lsep = _pad_t(lse.reshape(b * h, t_q), block_q)[:, None, :]
    deltap = _pad_t(delta.reshape(b * h, t_q), block_q)[:, None, :]

    q_spec, k_spec, v_spec, o_spec, col_spec, mask_spec = _spec_family(
        block_q, block_k, dk, dv, h)
    in_specs = [q_spec, k_spec, v_spec, o_spec, col_spec, col_spec]
    operands = [qp, kp, vp, dop, lsep, deltap]
    if kv_mask is not None:
        in_specs.append(mask_spec)
        operands.append(_mask_rows(kv_mask, block_k))
    if select is not None:
        for one, into in zip(_select_operand(select, block_q, block_k, h),
                             (in_specs, operands)):
            into.append(one)

    def call(kernel, by_key, out_specs, out_shape, scratch_shapes):
        return _scheduled_call(
            functools.partial(
                kernel, scale=_scale(scale, dk), causal=causal,
                block_q=block_q, block_k=block_k, t_q_real=t_q,
                t_k_real=t_k, with_mask=kv_mask is not None,
                with_select=select is not None),
            (t_q, t_k, block_q, block_k, causal, by_key), b * h, in_specs,
            out_specs, out_shape, scratch_shapes, interpret, operands)

    g_q = call(_bwd_dq_kernel, False, q_spec,
               jax.ShapeDtypeStruct(qp.shape, q.dtype),
               [pltpu.VMEM((block_q, dk), jnp.float32)])
    # dk/dv: columns of pairs, q innermost (the accumulation axis)
    g_k, g_v = call(_bwd_dkv_kernel, True, (k_spec, v_spec),
                    (jax.ShapeDtypeStruct(kp.shape, k.dtype),
                     jax.ShapeDtypeStruct(vp.shape, v.dtype)),
                    [pltpu.VMEM((block_k, dk), jnp.float32),
                     pltpu.VMEM((block_k, dv), jnp.float32)])
    return (g_q[:, :t_q].reshape(b, h, t_q, dk),
            g_k[:, :t_k].reshape(b, h, t_k, dk),
            g_v[:, :t_k].reshape(b, h, t_k, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False, block_q=None,
                    block_k=None, interpret: bool = False, scale=None):
    """Flash attention, Pallas forward AND backward.

    The forward saves (q, k, v, out, lse); the backward recomputes each
    softmax block in VMEM from those residuals — memory stays O(T)
    end-to-end and nothing quadratic touches HBM in either direction.
    """
    return flash_attention_fwd_pallas(q, k, v, causal=causal,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret, scale=scale)


def _named_residuals(out, lse):
    """``out`` and ``lse`` under ``RESIDUAL_NAMES``: the identity, but
    for a ``jax.checkpoint`` policy that keeps those names."""
    return tuple(checkpoint_name(x, name)
                 for x, name in zip((out, lse), RESIDUAL_NAMES))


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret, scale):
    out, lse = _named_residuals(*flash_attention_fwd_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, return_lse=True, scale=scale))
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, scale, res, g):
    q, k, v, out, lse = res
    return flash_attention_bwd_pallas(
        q, k, v, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, scale=scale)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention_masked(q, k, v, maskf, causal, block_q, block_k,
                            interpret, scale):
    return flash_attention_fwd_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, kv_mask=maskf, scale=scale)


def _fam_fwd(q, k, v, maskf, causal, block_q, block_k, interpret, scale):
    out, lse = _named_residuals(*flash_attention_fwd_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, return_lse=True, kv_mask=maskf, scale=scale))
    return out, (q, k, v, out, lse, maskf)


def _fam_bwd(causal, block_q, block_k, interpret, scale, res, g):
    q, k, v, out, lse, maskf = res
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, kv_mask=maskf, scale=scale)
    return dq, dk, dv, jnp.zeros_like(maskf)


_flash_attention_masked.defvjp(_fam_fwd, _fam_bwd)


def flash_attention_masked(q, k, v, kv_mask=None, causal: bool = False,
                           block_q=None, block_k=None,
                           interpret="auto", scale=None):
    """`flash_attention` with an optional (B, T_k) key-validity mask —
    the entry the sequence tower / Ulysses paths use (the mask rides as
    f32 0/1 so the custom_vjp plumbing stays all-float; its cotangent
    is zero). ``interpret="auto"`` compiles on TPU and falls back to
    the Pallas interpreter elsewhere (CPU tests). ``scale`` multiplies
    the scores: a Python number, ``1 / sqrt(key width)`` where None."""
    if interpret == "auto":
        interpret = jax.default_backend() != "tpu"
    if kv_mask is None:
        return flash_attention(q, k, v, causal, block_q, block_k, interpret,
                               scale)
    return _flash_attention_masked(
        q, k, v, kv_mask.astype(jnp.float32), causal, block_q, block_k,
        interpret, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention_selected(q, k, v, select, block_q, block_k, interpret,
                              scale):
    return flash_attention_fwd_pallas(
        q, k, v, causal=True, block_q=block_q, block_k=block_k,
        interpret=interpret, return_lse=True, scale=scale, select=select)


def _fas_fwd(q, k, v, select, block_q, block_k, interpret, scale):
    out, lse = _named_residuals(*flash_attention_fwd_pallas(
        q, k, v, causal=True, block_q=block_q, block_k=block_k,
        interpret=interpret, return_lse=True, scale=scale, select=select))
    return (out, lse), (q, k, v, out, lse, select)


def _fas_bwd(block_q, block_k, interpret, scale, res, g):
    q, k, v, out, lse, select = res
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, out, lse, g[0], causal=True, block_q=block_q,
        block_k=block_k, interpret=interpret, scale=scale, select=select)
    return dq, dk, dv, None


_flash_attention_selected.defvjp(_fas_fwd, _fas_bwd)


def flash_attention_selected(q, k, v, select, block_q=None, block_k=None,
                             interpret="auto", scale=None):
    """Causal attention over the keys ``select`` names: (B, T, T) int8,
    nonzero where query ``t`` attends key ``s``, one selection for all
    the heads; an entry above the diagonal attends nothing whatever it
    holds. The same three kernels over the same causal schedule as
    :func:`flash_attention`, each handed the selection's block of its
    scheduled pair beside the positional mask: a pair is walked whether
    or not any of its keys is selected. Returns ``(out, lse)``: the
    output and the float32 logsumexp of every query's selected scores
    (B, H, T), for a caller that rebuilds the probabilities;
    ``lse`` takes no cotangent (read it under ``stop_gradient``). Both
    carry ``RESIDUAL_NAMES``; the selection has no gradient."""
    if interpret == "auto":
        interpret = jax.default_backend() != "tpu"
    return _flash_attention_selected(q, k, v, select.astype(jnp.int8),
                                     block_q, block_k, interpret, scale)
