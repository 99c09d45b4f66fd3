"""Pallas kernels for a hyper-connection's passes over the stream state.

A hyper-connected sublayer (``models/hybrid_seq._HyperLayer``) keeps
``n`` residual streams of ``C`` features a position. Here the state is
``x`` (.., n C): stream ``j`` is the lane range ``[j C, (j+1) C)`` of a
position's row, so a position's flattened streams ``z`` are the row
itself and no kernel sees a (.., n, C) block, whose four sublanes would
be padded to sixteen. Written in ``jnp`` the sublayer's maps cast the
whole state to float32 for a product onto ``W = 2 n + n^2`` columns,
and its read-out and mixing are sums of broadcast products that XLA
runs as separate float32 passes, forward and backward. Here every pass
over the state is one kernel that reads it once in its own dtype, does
the arithmetic in float32 in VMEM and writes once:

``read_out(x, phi, scale, bias)`` -> ``(u, m, carry)``
    ``m = (z phi) rsqrt(mean z^2 + eps)`` (.., W), float32;
    ``pre = sigmoid(scale m[:n] + bias)``; ``u = sum_j pre[j] x[j]``
    (.., C) in float32 (below); ``carry`` is ``x`` itself, for
    ``mix``. One kernel over whole rows: ``z phi`` on the MXU from the
    block as it is (``phi`` as three bfloat16 parts, stacked, whose sum
    is the float32 ``phi``: a bfloat16 state is exact, so one product
    onto ``3 W`` columns gives what ``Precision.HIGHEST`` gives).
``mix(carry, y, res, post)`` -> ``x'``
    ``x'[i] = sum_j res[i, j] x[j] + post[i] y``, cast once. Each
    feature column on its own: blocked over rows, features and the
    output stream, the four input blocks fetched once for the four.

Both are ``jax.custom_vjp``s whose residuals are their inputs (and the
``m``, ``r`` of the read-out), so autodiff unrolls nothing:

* the mix's backward is one kernel from ``g = dL/dx'``: ``dcarry[j] =
  sum_i res[i, j] g[i]``, ``dy = sum_i post[i] g[i]``, ``dpost[i] =
  <g[i], y>``, ``dres[i, j] = <g[i], x[j]>``;
* the read-out's backward, once the mixer's has given ``du``: a kernel
  for ``dpre[j] = <du, x[j]>``, the sigmoid's and the norm's small
  algebra in ``jnp``, and one kernel that writes the state's whole
  gradient ``dx[j] = dcarry[j] + pre[j] du + r (dm phi^T)[j] - (r^2 /
  (n C)) <dm, m> z[j]`` and accumulates ``dphi = sum_t z_t^T (r dm)_t``
  over the row blocks. That ``x`` reaches ``mix`` only as ``carry`` is
  what lets this kernel add the mix's part in VMEM: two readers of
  ``x`` would leave the sum to a pass of XLA's.

``u`` leaves in float32, not in the state's dtype: the mixer's norm
works in float32 anyway, and a ``u`` rounded to bfloat16 (with a ``du``
rounded on the way back) is no longer parallel to the identical streams
the first sublayer reads, so that ``pre``'s leaves there, whose true
gradient is zero, get the rounding's: 0.19 of the median leaf's
gradient on one seed of seven on the chip, where the comparison that
decides ``correct`` allows a leaf 0.25 (XLA, allowed excess precision,
kept the unfused formula's ``u`` in float32 through its fusions and read
0.04-0.10). It costs a quarter of a state more written and read.

``post`` and ``res`` (sigmoid, clip, Sinkhorn) are the caller's, in
``jnp`` on (.., W) numbers. Products of float32 small arrays with the
state go to the MXU as stacked bfloat16 parts: all six cross terms
where the state is float32, the three leading ones into a bfloat16
``dx`` (2^-16 under a result rounded to 2^-9).

Each entry is jitted, so that the sublayers of a tower, which share one
shape, trace each kernel once. On the CPU the kernels run interpreted
(``interpret="auto"``). Times alone on the chip: ``tools/
hyper_kernel_times.py``; PERF.md §6 "PR 38".
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from persia_tpu import ops

F32, BF16 = jnp.float32, jnp.bfloat16
_LANES = 128

# rows a block: the read-out's blocks hold whole rows (n C features),
# the others ``FEATURES`` of them a stream
READ_ROWS = 128
ROWS = 256
FEATURES = 896
# the read-out's two row blocks in flight, ``phi``'s parts and the
# float32 slices pass the 16 MB a call gets by default; the mix's
# backward (six blocks in, two out, twenty rows of partial sums) passes
# them by 0.15 MB. The others fit: twice the rows or the features would
# not, for 5 % of their time (PERF.md §6 "PR 38")
READ_VMEM_LIMIT = 48 * 1024 * 1024
MIX_BWD_VMEM_LIMIT = 32 * 1024 * 1024


def _round_up(x, to):
    return -(-x // to) * to


def _row_block(rows, want):
    """Rows a block (sublane-aligned for bfloat16) and the padded count."""
    block = want if rows >= want else _round_up(rows, 16)
    return block, _round_up(rows, block)


def _feature_block(c, want):
    """The widest multiple of a lane tile that divides ``c``, up to
    ``want``; ``c`` itself where no tile divides it (interpreted only:
    the chip's compiler refuses a block that is neither)."""
    if c % _LANES:
        return c
    return max(b for b in range(_LANES, min(c, want) + 1, _LANES)
               if c % b == 0)


def _pad_rows(a, rows, axis=0):
    if a.shape[axis] == rows:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, rows - a.shape[axis])
    return jnp.pad(a, pad)


def _bf16_parts(a):
    """``a`` as a sum of bfloat16 arrays, leading part first: itself if
    it is bfloat16, else three (a float32's 24 bits, eight each, cut
    and not rounded)."""
    if a.dtype == BF16:
        return [a]
    out, rest = [], a.astype(F32)
    for _ in range(3):
        # the top sixteen bits, by a mask: a cast there and back is one
        # that XLA on the chip takes out (excess precision allowed),
        # which leaves the later parts zero
        top = lax.bitcast_convert_type(
            lax.bitcast_convert_type(rest, jnp.uint32)
            & jnp.uint32(0xFFFF0000), F32)
        out.append(top.astype(BF16))
        rest = rest - top
    return out


def _streams(ref_or_value, n):
    c = ref_or_value.shape[-1] // n
    return [ref_or_value[:, j * c:(j + 1) * c] for j in range(n)]


def _column(a, k):
    return a[:, k:k + 1]


def _folded(p):
    """``p`` (rows, lanes) summed onto one lane tile where whole tiles
    divide it (adds on the VPU; the cross-lane sum waits), else its row
    sums."""
    width = p.shape[-1]
    if width % _LANES:
        return jnp.sum(p, axis=-1, keepdims=True)
    return functools.reduce(
        jnp.add, [p[:, k:k + _LANES] for k in range(0, width, _LANES)])


def _spread(columns, width):
    """(rows, 1) arrays side by side as (rows, width), zeros beyond."""
    lane = lax.broadcasted_iota(jnp.int32, (columns[0].shape[0], width), 1)
    out = jnp.zeros(lane.shape, F32)
    for k, col in enumerate(columns):
        out = jnp.where(lane == k, col, out)
    return out


# --- the read-out ------------------------------------------------------------


def _read_out_kernel(x_ref, phi_ref, ab_ref, u_ref, m_ref, r_ref, *, n, eps):
    w = m_ref.shape[-1]
    s = jnp.zeros(m_ref.shape, F32)
    for k, part in enumerate(_bf16_parts(x_ref[...])):
        # part k of x meets the parts of phi down to the float32's last
        # bits: stacked, one product onto (3 - k) W columns
        groups = 3 - k
        prod = lax.dot_general(part, phi_ref[0:groups * w, :],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)
        for g in range(groups):
            s = s + prod[:, g * w:(g + 1) * w]
    xs = [xj.astype(F32) for xj in _streams(x_ref, n)]
    q = functools.reduce(jnp.add, [
        jnp.sum(xj * xj, axis=-1, keepdims=True) for xj in xs])
    r = lax.rsqrt(q / x_ref.shape[-1] + eps)
    m = s * r
    pre = jax.nn.sigmoid(ab_ref[0:1, :] * m[:, :n] + ab_ref[1:2, :])
    u_ref[...] = functools.reduce(jnp.add, [
        _column(pre, j) * xj for j, xj in enumerate(xs)])
    m_ref[...] = m
    r_ref[...] = r


@functools.partial(jax.jit, static_argnames=(
    "streams", "eps", "interpret", "rows"))
def read_out_fwd(x, phi, scale, bias, *, streams, eps, interpret,
                 rows=READ_ROWS):
    """``x`` (R, n C), ``phi`` (n C, W), ``scale`` (1,), ``bias`` (n,)
    of the read-out's map -> ``u`` (R, C), ``m`` (R, W), ``r`` (R, 1),
    all float32."""
    n, (count, width) = streams, x.shape
    w = phi.shape[-1]
    block, padded = _row_block(count, rows)
    ab = jnp.stack([jnp.broadcast_to(scale.astype(F32), (n,)),
                    bias.astype(F32)])
    call = pl.pallas_call(
        functools.partial(_read_out_kernel, n=n, eps=eps),
        grid=(padded // block,),
        in_specs=[pl.BlockSpec((block, width), lambda i: (i, 0)),
                  pl.BlockSpec((3 * w, width), lambda i: (0, 0)),
                  pl.BlockSpec((2, n), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block, width // n), lambda i: (i, 0)),
                   pl.BlockSpec((block, w), lambda i: (i, 0)),
                   pl.BlockSpec((block, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((padded, width // n), F32),
                   jax.ShapeDtypeStruct((padded, w), F32),
                   jax.ShapeDtypeStruct((padded, 1), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=READ_VMEM_LIMIT),
        interpret=interpret,
    )
    with jax.named_scope("hyper_maps"):   # the call's name in a trace
        u, m, r = call(_pad_rows(x, padded),
                       jnp.concatenate(_bf16_parts(phi.T), axis=0), ab)
    return u[:count], m[:count], r[:count]


def _dpre_kernel(*refs, n):
    x_refs, (du_ref, out_ref, acc_ref) = refs[:n], refs[n:]
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    du = du_ref[...].astype(F32)
    for j in range(n):
        acc_ref[j] += _folded(du * x_refs[j][...].astype(F32))

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = _spread(
            [jnp.sum(acc_ref[j], axis=-1, keepdims=True) for j in range(n)],
            n)


def _stream_specs(n, block, fb, per_stream):
    """One block spec a stream for a state passed ``n`` times, over a
    grid of (row block, feature block, ..): stream ``j``'s ``fb``
    features of the feature block."""
    def spec(j):
        return pl.BlockSpec((block, fb),
                            lambda r, k, *_: (r, j * per_stream + k))
    return [spec(j) for j in range(n)]


def _acc_lanes(fb):
    return _LANES if fb % _LANES == 0 else 1


@functools.partial(jax.jit, static_argnames=(
    "streams", "interpret", "rows", "features"))
def read_out_dpre(x, du, *, streams, interpret, rows=ROWS,
                  features=FEATURES):
    """``dpre[j] = <du, x[j]>`` (R, n), float32."""
    n, (count, width) = streams, x.shape
    c = width // n
    block, padded = _row_block(count, rows)
    fb = _feature_block(c, features)
    call = pl.pallas_call(
        functools.partial(_dpre_kernel, n=n),
        grid=(padded // block, c // fb),
        in_specs=_stream_specs(n, block, fb, c // fb)
        + [pl.BlockSpec((block, fb), lambda i, k: (i, k))],
        out_specs=pl.BlockSpec((block, n), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, n), F32),
        scratch_shapes=[pltpu.VMEM((n, block, _acc_lanes(fb)), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope("hyper_maps"):   # the call's name in a trace
        out = call(*[_pad_rows(x, padded)] * n, _pad_rows(du, padded))
    return out[:count]


# the cross terms of two float32 factors as bfloat16 parts, leading
# first: all six that reach float32's last bits, or the three that
# reach 2^-16, enough under a result that is rounded to bfloat16
_CROSS = {3: ((0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (2, 0)),
          2: ((0, 0), (0, 1), (1, 0))}


def _read_out_bwd_kernel(dc_ref, x_ref, du_ref, rows_ref, a_ref, b_ref,
                         t_ref, dx_ref, dphi_ref, *, per_stream):
    i, k, j = (pl.program_id(d) for d in range(3))
    at = j * per_stream + k
    w = dphi_ref.shape[1]

    @pl.when(i == 0)
    def _():
        dphi_ref[at] = jnp.zeros(dphi_ref.shape[1:], F32)

    x = x_ref[...]
    z = x.astype(F32)
    coef = rows_ref[...]        # pre[j], and the norm's factor of z
    dx = (dc_ref[...].astype(F32) + _column(coef, 0) * du_ref[...].astype(F32)
          + jnp.dot(a_ref[...], b_ref[...], preferred_element_type=F32)
          + _column(coef, 1) * z)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    acc = jnp.zeros(dphi_ref.shape[1:], F32)
    for p, part in enumerate(_bf16_parts(x)):
        groups = 3 - p
        prod = jnp.dot(t_ref[0:groups * w, :], part,
                       preferred_element_type=F32)
        for g in range(groups):
            acc = acc + prod[g * w:(g + 1) * w, :]
    dphi_ref[at] += acc


@functools.partial(jax.jit, static_argnames=(
    "streams", "interpret", "rows", "features"))
def read_out_bwd(dcarry, x, du, pre, factor, rdm, phi, *, streams, interpret,
                 rows=ROWS, features=FEATURES):
    """The state's gradient and ``phi``'s: ``dx[j] = dcarry[j] + pre[j]
    du + (rdm phi^T)[j] + factor z[j]`` in the state's dtype, ``dphi =
    z^T rdm`` (n C, W) in float32. ``pre`` (R, n), ``factor`` (R, 1),
    ``rdm`` (R, W): the caller's small algebra."""
    n, (count, width) = streams, x.shape
    c, w = width // n, phi.shape[-1]
    block, padded = _row_block(count, rows)
    fb = _feature_block(c, features)
    per_stream = c // fb
    cross = _CROSS[2 if x.dtype == BF16 else 3]
    rdm_parts = _bf16_parts(_pad_rows(rdm, padded))
    phi_parts = _bf16_parts(phi.T)
    a = jnp.concatenate([rdm_parts[p] for p, _ in cross], axis=1)
    b = jnp.concatenate([phi_parts[q] for _, q in cross], axis=0)
    t = jnp.concatenate([part.T for part in rdm_parts], axis=0)
    # [j, row]: pre[j] beside the norm's factor
    coef = jnp.stack([_pad_rows(jnp.concatenate(
        [pre[:, j:j + 1], factor], axis=1).astype(F32), padded)
        for j in range(n)])

    def state(i, k, j):
        return (i, j * per_stream + k)

    call = pl.pallas_call(
        functools.partial(_read_out_bwd_kernel, per_stream=per_stream),
        grid=(padded // block, per_stream, n),
        in_specs=[pl.BlockSpec((block, fb), state),
                  pl.BlockSpec((block, fb), state),
                  pl.BlockSpec((block, fb), lambda i, k, j: (i, k)),
                  pl.BlockSpec((None, block, 2), lambda i, k, j: (j, i, 0)),
                  pl.BlockSpec((block, a.shape[1]), lambda i, k, j: (i, 0)),
                  pl.BlockSpec((b.shape[0], fb),
                               lambda i, k, j: (0, j * per_stream + k)),
                  pl.BlockSpec((3 * w, block), lambda i, k, j: (0, i))],
        out_specs=[pl.BlockSpec((block, fb), state),
                   pl.BlockSpec((n * per_stream, w, fb),
                                lambda i, k, j: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((padded, width), x.dtype),
                   jax.ShapeDtypeStruct((n * per_stream, w, fb), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )
    with jax.named_scope("hyper_maps"):   # the call's name in a trace
        dx, dphi = call(_pad_rows(dcarry, padded), _pad_rows(x, padded),
                        _pad_rows(du, padded), coef, a, b, t)
    return dx[:count], dphi.transpose(0, 2, 1).reshape(width, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _read_out(x, phi, scale, bias, streams, eps, interpret):
    u, m, _ = read_out_fwd(x, phi, scale, bias, streams=streams, eps=eps,
                           interpret=interpret)
    return u, m, x


def _read_out_fwd_rule(x, phi, scale, bias, streams, eps, interpret):
    u, m, r = read_out_fwd(x, phi, scale, bias, streams=streams, eps=eps,
                           interpret=interpret)
    return (u, m, x), (x, phi, scale, bias, m, r)


@functools.partial(jax.jit, static_argnames=("streams", "interpret"))
def _read_out_grads(x, phi, scale, bias, m, r, du, dm, dcarry, *, streams,
                    interpret):
    n = streams
    pre = jax.nn.sigmoid(scale * m[:, :n] + bias)
    dlogit = (read_out_dpre(x, du, streams=n, interpret=interpret)
              * pre * (1 - pre))
    dm = dm.at[:, :n].add(scale * dlogit)
    # dL/ds = r dm; dL/dr = <dm, s> with s = m / r, and r = (mean z^2 +
    # eps)^(-1/2) gives dr/dz = -r^3 z / (n C)
    factor = -(r * r / x.shape[-1]) * jnp.sum(dm * m, axis=-1,
                                               keepdims=True)
    dx, dphi = read_out_bwd(dcarry, x, du, pre, factor, r * dm, phi,
                            streams=n, interpret=interpret)
    return (dx, dphi.astype(phi.dtype),
            jnp.sum(dlogit * m[:, :n]).reshape(scale.shape).astype(
                scale.dtype),
            jnp.sum(dlogit, axis=0).astype(bias.dtype))


def _read_out_bwd_rule(streams, eps, interpret, residuals, cotangents):
    return _read_out_grads(*residuals, *cotangents, streams=streams,
                           interpret=interpret)


_read_out.defvjp(_read_out_fwd_rule, _read_out_bwd_rule)


# --- the mix -----------------------------------------------------------------


def _mix_kernel(*refs, n):
    x_refs, (y_ref, coef_ref, out_ref) = refs[:n], refs[n:]
    coef = coef_ref[...]        # res[i, :] and post[i] of this step's i
    acc = functools.reduce(jnp.add, [
        _column(coef, j) * x_refs[j][...].astype(F32) for j in range(n)])
    out_ref[...] = (acc + _column(coef, n) * y_ref[...].astype(F32)).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "rows", "features"))
def mix_fwd(x, y, res, post, *, interpret, rows=ROWS, features=FEATURES):
    """``x`` (R, n C), ``y`` (R, C), ``res`` (R, n n) row-major,
    ``post`` (R, n) -> ``x'`` (R, n C) in ``x``'s dtype."""
    (count, width), n = x.shape, post.shape[-1]
    c = width // n
    block, padded = _row_block(count, rows)
    fb = _feature_block(c, features)
    per_stream = c // fb
    # [i, row]: res[i, :] beside post[i]
    coef = _pad_rows(jnp.concatenate(
        [res.reshape(count, n, n), post[:, :, None]],
        axis=-1).astype(F32).transpose(1, 0, 2), padded, axis=1)
    call = pl.pallas_call(
        functools.partial(_mix_kernel, n=n),
        grid=(padded // block, per_stream, n),
        in_specs=_stream_specs(n, block, fb, per_stream)
        + [pl.BlockSpec((block, fb), lambda r, k, i: (r, k)),
           pl.BlockSpec((None, block, n + 1), lambda r, k, i: (i, r, 0))],
        out_specs=pl.BlockSpec((block, fb),
                               lambda r, k, i: (r, i * per_stream + k)),
        out_shape=jax.ShapeDtypeStruct((padded, width), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope("hyper_mix"):   # the call's name in a trace
        out = call(*[_pad_rows(x, padded)] * n, _pad_rows(y, padded), coef)
    return out[:count]


def _mix_bwd_kernel(*refs, n):
    g_refs = refs[:n]
    x_ref, y_ref, coef_ref, dc_ref, dy_ref, small_ref, acc_ref = refs[n:]
    k, j = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(1) - 1

    @pl.when((k == 0) & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = [ref[...].astype(F32) for ref in g_refs]
    coef = coef_ref[...]        # res[:, j] of this step's j, then post
    dc_ref[...] = functools.reduce(jnp.add, [
        _column(coef, i) * g[i] for i in range(n)]).astype(dc_ref.dtype)
    xj = x_ref[...].astype(F32)
    for i in range(n):          # dres[i, j], at the dynamic j
        acc_ref[j * n + i] += _folded(g[i] * xj)

    @pl.when(j == 0)
    def _():
        y = y_ref[...].astype(F32)
        dy_ref[...] = functools.reduce(jnp.add, [
            _column(coef, n + i) * g[i] for i in range(n)]).astype(
                dy_ref.dtype)
        for i in range(n):      # dpost[i]
            acc_ref[n * n + i] += _folded(g[i] * y)

    @pl.when((k == last) & (j == n - 1))
    def _():
        small_ref[...] = _spread(
            [jnp.sum(acc_ref[p], axis=-1, keepdims=True)
             for p in range(n * n + n)], small_ref.shape[-1])


@functools.partial(jax.jit, static_argnames=("interpret", "rows", "features"))
def mix_bwd(x, y, res, post, g, *, interpret, rows=ROWS, features=FEATURES):
    """From ``g = dL/dx'``: ``dx`` (R, n C) and ``dy`` (R, C) in their
    arrays' dtypes, ``dres`` (R, n n) and ``dpost`` (R, n) in float32."""
    (count, width), n = x.shape, post.shape[-1]
    c = width // n
    block, padded = _row_block(count, rows)
    fb = _feature_block(c, features)
    per_stream = c // fb
    # [j, row]: res[:, j] beside post
    coef = _pad_rows(jnp.concatenate(
        [res.reshape(count, n, n).transpose(2, 0, 1),
         jnp.broadcast_to(post, (n, count, n))],
        axis=-1).astype(F32), padded, axis=1)
    call = pl.pallas_call(
        functools.partial(_mix_bwd_kernel, n=n),
        grid=(padded // block, per_stream, n),
        in_specs=_stream_specs(n, block, fb, per_stream)
        + [pl.BlockSpec((block, fb),
                        lambda r, k, j: (r, j * per_stream + k)),
           pl.BlockSpec((block, fb), lambda r, k, j: (r, k)),
           pl.BlockSpec((None, block, 2 * n), lambda r, k, j: (j, r, 0))],
        out_specs=[pl.BlockSpec((block, fb),
                                lambda r, k, j: (r, j * per_stream + k)),
                   pl.BlockSpec((block, fb), lambda r, k, j: (r, k)),
                   pl.BlockSpec((block, n * n + n), lambda r, k, j: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((padded, width), x.dtype),
                   jax.ShapeDtypeStruct((padded, c), y.dtype),
                   jax.ShapeDtypeStruct((padded, n * n + n), F32)],
        scratch_shapes=[pltpu.VMEM((n * n + n, block, _acc_lanes(fb)), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=MIX_BWD_VMEM_LIMIT),
        interpret=interpret,
    )
    with jax.named_scope("hyper_mix"):   # the call's name in a trace
        dx, dy, small = call(*[_pad_rows(g, padded)] * n,
                             _pad_rows(x, padded), _pad_rows(y, padded), coef)
    # the scratch holds [j n + i]: dres[i, j]
    dres = small[:count, :n * n].reshape(count, n, n).transpose(0, 2, 1)
    return (dx[:count], dy[:count], dres.reshape(count, n * n),
            small[:count, n * n:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _mix(x, y, res, post, interpret):
    return mix_fwd(x, y, res, post, interpret=interpret)


def _mix_fwd_rule(x, y, res, post, interpret):
    return mix_fwd(x, y, res, post, interpret=interpret), (x, y, res, post)


def _mix_bwd_rule(interpret, residuals, g):
    x, y, res, post = residuals
    dx, dy, dres, dpost = mix_bwd(x, y, res, post, g, interpret=interpret)
    return dx, dy, dres.astype(res.dtype), dpost.astype(post.dtype)


_mix.defvjp(_mix_fwd_rule, _mix_bwd_rule)


# --- what the sublayer calls -------------------------------------------------


def read_out(x, phi, scale, bias, *, streams, eps, interpret="auto"):
    """``x`` (.., n C) in the compute dtype; ``phi`` (n C, W), float32:
    the three maps' projections side by side, the read-out's ``n``
    columns first; ``scale`` (1,) and ``bias`` (n,) of the read-out's
    map. Returns ``u`` (.., C) and ``m`` (.., W) in float32 and ``carry``,
    the state for :func:`mix` (hand ``mix`` this and not ``x``: the
    read-out's backward then writes the state's whole gradient in its
    one pass)."""
    lead = x.shape[:-1]
    u, m, carry = _read_out(x.reshape(-1, x.shape[-1]), phi, scale, bias,
                            streams, float(eps), ops.interpret(interpret))
    return (u.reshape(*lead, -1), m.reshape(*lead, -1),
            carry.reshape(x.shape))


def mix(carry, y, res, post, *, interpret="auto"):
    """``x'[i] = sum_j res[i, j] carry[j] + post[i] y`` (.., n C) in the
    state's dtype: ``y`` (.., C), ``res`` (.., n, n), ``post`` (.., n)."""
    n = post.shape[-1]
    out = _mix(carry.reshape(-1, carry.shape[-1]),
               y.reshape(-1, y.shape[-1]), res.reshape(-1, n * n),
               post.reshape(-1, n), ops.interpret(interpret))
    return out.reshape(carry.shape)
