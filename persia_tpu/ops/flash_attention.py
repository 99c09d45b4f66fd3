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

Kernel shape rules: dh is the lane axis of every block, one width for
queries, keys and values (any dh ≤ 128 works, full-axis blocks are
padded internally; a multiple of 128 above that too: the latent
attention tower's heads are 256 wide). Block sizes are multiples of the
128-lane width (``_clamp_block``) so every tile — bf16 (16, 128) included — and every per-row lane vector
(lse, delta, kv_mask ride as ``(.., 1, T)`` rows blocked ``(1, block)``)
meets the TPU lowering's (8, 128) block rule. T is padded to the k/q
block size by the wrapper; padded KEY positions are masked via the
static true-length, padded QUERY rows compute garbage that the wrapper
slices off.

Backward: Pallas too (jax.custom_vjp). The forward saves (q, k, v,
out, lse); `flash_attention_bwd_pallas` recomputes each softmax block
in VMEM from those residuals with the same schedule run twice — dq
accumulates across the k-grid, dk/dv across the q-grid. delta
(rowsum(dO·O)) is a cheap XLA reduce. Memory stays O(T) end to end.

The carry-bandwidth figures above are computed from shapes; kernel time
and roofline share on the chip: not measured (see PERF.md).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # large-finite: -inf NaNs the m-update on all-masked rows
_LANES = 128


def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                scale: float, causal: bool, block_q: int, block_k: int,
                t_k_real: int, n_k: int, with_lse: bool, with_mask: bool):
    if with_mask:
        mask_ref, rest = rest[0], rest[1:]
    o_ref, rest = rest[0], rest[1:]
    if with_lse:
        lse_ref, acc, m_scr, l_scr = rest
    else:
        acc, m_scr, l_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def _body():
        q = q_ref[0]                       # (block_q, dh) bf16/f32
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < t_k_real            # padded keys never attend
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if with_mask:
            mask = jnp.logical_and(mask, mask_ref[...] > 0)  # (1, bk) bcast
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                # (block_q, 128) lane-replicated
        m_cur = jnp.max(s, axis=1, keepdims=True)       # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)              # (bq, 128)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                   # (bq, bk) f32
        if with_mask:
            # a FULLY-masked row has s = m_new = NEG_INF everywhere, so
            # the subtraction above degenerates to exp(0)=1; zero it
            # (l stays 0 -> output 0, matching reference_attention)
            p = jnp.where(m_new[:, :1] > _NEG_INF / 2, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(
            p, axis=1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, dh)
        acc[...] = acc[...] * alpha[:, :1] + pv

    if causal:
        # blocks strictly above the diagonal contribute nothing — skip
        # their matmuls (their k/v DMAs still ride the pipeline; pruning
        # those too needs grid index-remapping, not worth it here)
        pl.when((qi + 1) * block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(ki == n_k - 1)
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


def _pad_t(x, block, axis=1):
    """Zero-pad ``axis`` up to a multiple of ``block``."""
    pad = (-x.shape[axis]) % block
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


def _mask_rows(kv_mask, block_k):
    """(B, T_k) key-validity mask -> (B, 1, T_k padded) f32 0/1 rows."""
    return _pad_t(kv_mask.astype(jnp.float32), block_k)[:, None, :]


def _spec_family(block_q, block_k, dh, h, q_minor: bool):
    """The four block-spec shapes every kernel here uses, for one grid
    order: q-tile, k-tile, per-q lane row (lse/delta, arrays shaped
    (BH, 1, T)), per-k lane row (kv_mask, (B, 1, T), batch axis =
    bh // h). The row arrays carry a unit sublane axis so the block's
    last two dims are (1 == full, block % 128 == 0) — a bare
    ``(1, block)`` block over ``(BH, T)`` does not lower for TPU.
    ``q_minor=True`` = grid (bh, qi, ki); ``False`` = (bh, ki, qi). One
    definition so a layout change cannot drift between the forward and
    the two backward calls."""
    if q_minor:
        def pos(bh, qi, ki):
            return qi, ki
    else:
        def pos(bh, ki, qi):
            return qi, ki
    return (
        pl.BlockSpec((1, block_q, dh), lambda *g: (g[0], pos(*g)[0], 0)),
        pl.BlockSpec((1, block_k, dh), lambda *g: (g[0], pos(*g)[1], 0)),
        pl.BlockSpec((None, 1, block_q),
                     lambda *g: (g[0], 0, pos(*g)[0])),
        pl.BlockSpec((None, 1, block_k),
                     lambda *g, h=h: (g[0] // h, 0, pos(*g)[1])),
    )


def flash_attention_fwd_pallas(q, k, v, causal: bool = False,
                               block_q: int = 512, block_k: int = 512,
                               interpret: bool = False,
                               return_lse: bool = False,
                               kv_mask=None):
    """Forward Pallas flash attention. q/k/v: (B, H, T, Dh).

    With ``return_lse`` also returns the (B, H, T) logsumexp residual
    the backward kernels consume. ``kv_mask`` optional (B, T_k) of
    valid key positions; fully-masked query rows yield 0."""
    b, h, t_q, dh = q.shape
    t_k = k.shape[2]
    block_q = _clamp_block(block_q, t_q)
    block_k = _clamp_block(block_k, t_k)
    qp = _pad_t(q.reshape(b * h, t_q, dh), block_q)
    kp = _pad_t(k.reshape(b * h, t_k, dh), block_k)
    vp = _pad_t(v.reshape(b * h, t_k, dh), block_k)
    n_q = qp.shape[1] // block_q
    n_k = kp.shape[1] // block_k
    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / float(dh) ** 0.5, causal=causal,
        block_q=block_q, block_k=block_k, t_k_real=t_k, n_k=n_k,
        with_lse=return_lse, with_mask=kv_mask is not None)
    q_spec, k_spec, qrow_spec, krow_spec = _spec_family(
        block_q, block_k, dh, h, q_minor=True)
    in_specs = [q_spec, k_spec, k_spec]
    operands = [qp, kp, vp]
    if kv_mask is not None:
        # (B, T_k) f32 0/1; the grid's bh axis maps back to batch bh//h
        in_specs.append(krow_spec)
        operands.append(_mask_rows(kv_mask, block_k))
    o_spec = q_spec
    o_shape = jax.ShapeDtypeStruct((b * h, n_q * block_q, dh), q.dtype)
    if return_lse:
        out_specs = (o_spec, qrow_spec)
        out_shape = (o_shape, jax.ShapeDtypeStruct(
            (b * h, 1, n_q * block_q), jnp.float32))
    else:  # serving path: no lse output, no wasted HBM write
        out_specs, out_shape = o_spec, o_shape
    res = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    if return_lse:
        out, lse = res
        return (out[:, :t_q].reshape(b, h, t_q, dh),
                lse[:, 0, :t_q].reshape(b, h, t_q))
    return res[:, :t_q].reshape(b, h, t_q, dh)


def _masked_p(q, k, lse, *, scale, causal, block_q, block_k, qi, ki,
              t_q_real, t_k_real, mask_row=None):
    """Recompute the (block_q, block_k) softmax block from q/k/lse with
    padding + causal + optional key masking — shared by both backward
    kernels. Fully-masked rows (lse pinned at NEG_INF by the forward)
    are forced to p=0, not the exp(0)=1 the raw arithmetic gives."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.logical_and(q_pos < t_q_real, k_pos < t_k_real)
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if mask_row is not None:
        mask = jnp.logical_and(mask, mask_row > 0)      # (1, bk) bcast
    s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)
    return jnp.where(lse > _NEG_INF / 2, p, 0.0)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, scale: float, causal: bool,
                   block_q: int, block_k: int, t_q_real: int,
                   t_k_real: int, n_k: int, with_mask: bool):
    if with_mask:
        mask_ref, dq_ref, dq_acc = rest
    else:
        mask_ref, (dq_ref, dq_acc) = None, rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body():
        # lse/delta ride in (1, block_q) lane-major rows (a (T, 1)
        # layout would be 128-lane padded in HBM); transpose to columns
        lse = jnp.transpose(lse_ref[...])               # (bq, 1)
        delta = jnp.transpose(delta_ref[...])
        p = _masked_p(q_ref[0], k_ref[0], lse, scale=scale,
                      causal=causal, block_q=block_q, block_k=block_k,
                      qi=qi, ki=ki, t_q_real=t_q_real, t_k_real=t_k_real,
                      mask_row=None if mask_ref is None else mask_ref[...])
        do = do_ref[0]
        dp = jax.lax.dot_general(                       # dO @ V^T
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                           # (bq, bk)
        dq_acc[...] += jax.lax.dot_general(             # ds @ K
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when((qi + 1) * block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale: float,
                    causal: bool, block_q: int, block_k: int,
                    t_q_real: int, t_k_real: int, n_q: int,
                    with_mask: bool):
    if with_mask:
        mask_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        mask_ref, (dk_ref, dv_ref, dk_acc, dv_acc) = None, rest
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body():
        q = q_ref[0]
        lse = jnp.transpose(lse_ref[...])               # (bq, 1)
        delta = jnp.transpose(delta_ref[...])
        p = _masked_p(q, k_ref[0], lse, scale=scale,
                      causal=causal, block_q=block_q, block_k=block_k,
                      qi=qi, ki=ki, t_q_real=t_q_real, t_k_real=t_k_real,
                      mask_row=None if mask_ref is None else mask_ref[...])
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

    if causal:
        pl.when((qi + 1) * block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, out, lse, do, causal: bool = False,
                               block_q: int = 512, block_k: int = 512,
                               interpret: bool = False, kv_mask=None):
    """Pallas flash-attention backward: (dq, dk, dv).

    Same schedule as the forward, run twice: dq revisits its q-block
    accumulator across the k-grid; dk/dv revisit their k-block
    accumulators across the q-grid. The softmax block is recomputed
    from (q, k, lse) in VMEM — nothing quadratic ever touches HBM.
    """
    b, h, t_q, dh = q.shape
    t_k = k.shape[2]
    block_q = _clamp_block(block_q, t_q)
    block_k = _clamp_block(block_k, t_k)
    scale = 1.0 / float(dh) ** 0.5
    # delta_i = rowsum(dO_i * O_i) — cheap XLA elementwise+reduce
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                             # (b, h, t_q)
    qp = _pad_t(q.reshape(b * h, t_q, dh), block_q)
    kp = _pad_t(k.reshape(b * h, t_k, dh), block_k)
    vp = _pad_t(v.reshape(b * h, t_k, dh), block_k)
    dop = _pad_t(do.reshape(b * h, t_q, dh), block_q)
    lsep = _pad_t(lse.reshape(b * h, t_q), block_q)[:, None, :]
    deltap = _pad_t(delta.reshape(b * h, t_q), block_q)[:, None, :]
    n_q = qp.shape[1] // block_q
    n_k = kp.shape[1] // block_k

    maskp = None if kv_mask is None else _mask_rows(kv_mask, block_k)

    q_spec, k_spec, col_spec, mask_spec = _spec_family(
        block_q, block_k, dh, h, q_minor=True)
    in_specs = [q_spec, k_spec, k_spec, q_spec, col_spec, col_spec]
    operands = [qp, kp, vp, dop, lsep, deltap]
    if maskp is not None:
        in_specs.append(mask_spec)
        operands.append(maskp)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, t_q_real=t_q, t_k_real=t_k, n_k=n_k,
            with_mask=maskp is not None),
        grid=(b * h, n_q, n_k),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, n_q * block_q, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
    )(*operands)

    # dk/dv: k-block outermost, q innermost (the accumulation axis)
    q_spec2, k_spec2, col_spec2, mask_spec2 = _spec_family(
        block_q, block_k, dh, h, q_minor=False)
    in_specs2 = [q_spec2, k_spec2, k_spec2, q_spec2, col_spec2, col_spec2]
    operands2 = [qp, kp, vp, dop, lsep, deltap]
    if maskp is not None:
        in_specs2.append(mask_spec2)
        operands2.append(maskp)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, t_q_real=t_q, t_k_real=t_k, n_q=n_q,
            with_mask=maskp is not None),
        grid=(b * h, n_k, n_q),
        in_specs=in_specs2,
        out_specs=(k_spec2, k_spec2),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, n_k * block_k, dh), k.dtype),
            jax.ShapeDtypeStruct((b * h, n_k * block_k, dh), v.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((block_k, dh), jnp.float32),
                        pltpu.VMEM((block_k, dh), jnp.float32)],
        interpret=interpret,
    )(*operands2)
    return (dq[:, :t_q].reshape(b, h, t_q, dh),
            dk[:, :t_k].reshape(b, h, t_k, dh),
            dv[:, :t_k].reshape(b, h, t_k, dh))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    """Flash attention, Pallas forward AND backward.

    The forward saves (q, k, v, out, lse); the backward recomputes each
    softmax block in VMEM from those residuals — memory stays O(T)
    end-to-end and nothing quadratic touches HBM in either direction.
    """
    return flash_attention_fwd_pallas(q, k, v, causal=causal,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret)


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = flash_attention_fwd_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, return_lse=True)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return flash_attention_bwd_pallas(
        q, k, v, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention_masked(q, k, v, maskf, causal, block_q, block_k,
                            interpret):
    return flash_attention_fwd_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, kv_mask=maskf)


def _fam_fwd(q, k, v, maskf, causal, block_q, block_k, interpret):
    out, lse = flash_attention_fwd_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, return_lse=True, kv_mask=maskf)
    return out, (q, k, v, out, lse, maskf)


def _fam_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse, maskf = res
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, kv_mask=maskf)
    return dq, dk, dv, jnp.zeros_like(maskf)


_flash_attention_masked.defvjp(_fam_fwd, _fam_bwd)


def flash_attention_masked(q, k, v, kv_mask=None, causal: bool = False,
                           block_q: int = 512, block_k: int = 512,
                           interpret="auto"):
    """`flash_attention` with an optional (B, T_k) key-validity mask —
    the entry the sequence tower / Ulysses paths use (the mask rides as
    f32 0/1 so the custom_vjp plumbing stays all-float; its cotangent
    is zero). ``interpret="auto"`` compiles on TPU and falls back to
    the Pallas interpreter elsewhere (CPU tests)."""
    if interpret == "auto":
        interpret = jax.default_backend() != "tpu"
    if kv_mask is None:
        return flash_attention(q, k, v, causal, block_q, block_k, interpret)
    return _flash_attention_masked(
        q, k, v, kv_mask.astype(jnp.float32), causal, block_q, block_k,
        interpret)
