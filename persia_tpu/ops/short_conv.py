"""The short causal depthwise convolution of the sequence mixers, SiLU
after it and, where the mixer norms its heads, an l2 norm a head, as one
Pallas op with a written-out backward.

``y = silu(conv(x))``, tap ``j`` of ``taps`` (kernel, channels)
reading position ``t - (kernel - 1) + j`` of the same history, zeros
before its start; with ``head_dim``, each head of ``head_dim`` channels
``y / sqrt(sum of its squares + L2_EPS) * scale``; rounded to
``out_dtype``. Float32 from the input's rounding on: the taps' terms
summed first to last. The plain form adds a bias after them besides.

``DeltaAttention`` runs it on its query (normed, scaled), key (normed)
and value (``models/hybrid_seq``), none with a bias. ``SSMMixer`` runs
the plain form on its ``xBC`` stream (a bias, no norm): there XLA fuses
the convolution into the split of the projection and the scan's inputs,
and the kernel's one output cost those more than the kernel saved
(PERF.md §6, "PR 43"). In XLA's hands the same arithmetic is a dozen
float32 passes over the (batch, T, channels) array and, under autodiff,
four reductions over the positions for the taps' gradient, each
reading a float32 input and cotangent again (PERF.md §5, "PR 40": 66.6
ms a step of the delta-rule cell).

**The kernels.** ``short_conv`` is a ``jax.custom_vjp`` of two Pallas
calls over a grid of (batch, channel blocks, position blocks), the
channel block a multiple of 128 lanes and of ``head_dim``:

* The forward reads a block of ``x`` and the ``HALO`` rows before it
  (zeros at a history's start: a block's index map never leaves its
  history) and writes ``y`` once. A head, or 128 lanes where there is no
  norm, is worked at a time: the taps are sublane rolls of the block
  with its halo on top, the norm a lane sum.
* The backward reads ``x`` and ``dy`` once, with ``HALO`` rows of ``x``
  before the block and of ``x`` and ``dy`` after it: ``dx[t]`` needs
  ``dpre[t .. t + kernel - 1]``. It rebuilds the pre-activation in VMEM
  for the block and the rows after it, pulls ``dy`` back through the
  norm (``r (dn - n sum(n dn))`` a head, ``n`` the normed output, ``r``
  the reciprocal root) and SiLU, writes ``dx`` in ``x``'s dtype and
  sums the taps' gradient in float32 scratch along the position axis,
  written once a (batch, channel block). Rows after a history's end
  carry no gradient.

Nothing is named for ``nn.remat`` to keep: the op's residuals are its
inputs, so a rebuilt layer runs the forward call again, as it runs the
projections before it again.

Where no block divides the shapes (:func:`conv_blocks`: a history that
is no whole number of ``HALO`` rows, channels that are no multiple of
128 lanes and of ``head_dim``) the op is :func:`short_conv_plain`, the
``jax.numpy`` form: the kernels' oracle in the tests. The shapes alone
decide. On the CPU the kernels run interpreted. Times alone on the
chip: ``tools/conv_times.py``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from persia_tpu import ops

F32 = jnp.float32
L2_EPS = 1e-6       # under the root of a head's sum of squares
LANES = 128
# rows above and below a block that a grid step reads besides it: a
# bfloat16 tile's rows, and at least ``kernel - 1``
HALO = 16
# positions and channels a grid step: the widest that divides
BLOCK_T = (512, 256, 128, 64, 32, 16)
BLOCK_C = (512, 256, 128)


def short_conv_plain(x, taps, bias=None, head_dim=None, scale=1.0,
                     out_dtype=jnp.bfloat16):
    """The op in ``jax.numpy``: ``x`` (batch, T, channels), ``taps``
    (kernel, channels) float32, ``bias`` (channels,) float32 or None.
    Under autodiff its float32 intermediates stand in HBM."""
    kernel, t = taps.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (kernel - 1, 0), (0, 0)))
    y = sum(taps[j] * xp[:, j:j + t] for j in range(kernel))
    if bias is not None:
        y = y + bias
    y = jax.nn.silu(y)
    if head_dim is not None:
        bs, t, channels = y.shape
        y = y.reshape(bs, t, channels // head_dim, head_dim)
        y = y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
        y = (y * scale).reshape(bs, t, channels)
    return y.astype(out_dtype)


def conv_blocks(t, channels, head_dim, kernel):
    """The (positions, channels) a grid step of the kernels holds, or
    None where no block divides the shapes: the plain form's."""
    if kernel - 1 > HALO or (head_dim or LANES) % LANES:
        return None
    block_t = next((b for b in BLOCK_T if t % b == 0), None)
    block_c = next((b for b in BLOCK_C
                    if channels % b == 0 and b % (head_dim or LANES) == 0),
                   None)
    return None if None in (block_t, block_c) else (block_t, block_c)


def _shifted(ext, shift, top, rows):
    """``rows`` rows of ``ext`` from row ``top``, each the one ``shift``
    rows above it."""
    if shift:
        ext = pltpu.roll(ext, shift, axis=0)
    return ext[top:top + rows]


def _tapped(taps, shifted):
    """``sum_j taps[j] shifted[j]``, the terms first to last."""
    out = taps[0:1] * shifted[0]
    for j in range(1, len(shifted)):
        out = out + taps[j:j + 1] * shifted[j]
    return out


def _slabs(block_c, head_dim):
    """The lane ranges worked at a time: a head, or 128 lanes."""
    width = head_dim or LANES
    return [slice(lo, lo + width) for lo in range(0, block_c, width)]


def _forward_kernel(x_ref, before_ref, taps_ref, y_ref, *, head_dim, scale):
    rows, kernel = x_ref.shape[1], taps_ref.shape[0]
    first = pl.program_id(2) == 0
    for lanes in _slabs(x_ref.shape[2], head_dim):
        before = jnp.where(first, 0.0, before_ref[0, :, lanes].astype(F32))
        ext = jnp.concatenate([before, x_ref[0, :, lanes].astype(F32)])
        pre = _tapped(taps_ref[:, lanes],
                      [_shifted(ext, kernel - 1 - j, HALO, rows)
                       for j in range(kernel)])
        y = jax.nn.silu(pre)
        if head_dim is not None:
            y = y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                              + L2_EPS)
            y = y * scale
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)


def _backward_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     taps_ref, dx_ref, dtaps_ref, dtaps_acc, *, head_dim,
                     scale):
    rows, kernel = x_ref.shape[1], taps_ref.shape[0]
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        dtaps_acc[...] = jnp.zeros_like(dtaps_acc)

    # the rows after the block that lie past the history's end
    live = jnp.where(step == steps - 1, rows, rows + HALO)
    for lanes in _slabs(x_ref.shape[2], head_dim):
        before = jnp.where(step == 0, 0.0,
                           before_ref[0, :, lanes].astype(F32))
        ext = jnp.concatenate([before, x_ref[0, :, lanes].astype(F32),
                               after_ref[0, :, lanes].astype(F32)])
        # the block's rows and the halo's after it
        shifted = [_shifted(ext, kernel - 1 - j, HALO, rows + HALO)
                   for j in range(kernel)]
        taps = taps_ref[:, lanes]
        pre = _tapped(taps, shifted)
        dy = jnp.concatenate([dy_ref[0, :, lanes].astype(F32),
                              dy_after_ref[0, :, lanes].astype(F32)])
        sig = jax.nn.sigmoid(pre)
        if head_dim is not None:
            a = pre * sig
            r = lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
            n, dn = a * r, dy * scale
            dy = r * (dn - n * jnp.sum(n * dn, axis=-1, keepdims=True))
        dpre = dy * (sig * (1.0 + pre * (1.0 - sig)))
        at = lax.broadcasted_iota(jnp.int32, dpre.shape, 0)
        dpre = jnp.where(at < live, dpre, 0.0)
        # dx[t] = sum_j taps[j] dpre[t + kernel - 1 - j]
        later = [_shifted(dpre, (rows + HALO - (kernel - 1 - j))
                          % (rows + HALO), 0, rows) for j in range(kernel)]
        dx = _tapped(taps, later)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        own = dpre[:rows]
        for j in range(kernel):
            dtaps_acc[j:j + 1, lanes] += jnp.sum(
                own * shifted[j][:rows], axis=0, keepdims=True)

    @pl.when(step == steps - 1)
    def _():
        dtaps_ref[0] = dtaps_acc[...]


def _specs(t, kernel, blocks):
    """Block specs over grid (batch, channel blocks, position blocks):
    a block of a (batch, T, channels) array, the ``HALO`` rows before
    it and those after it (inside the array: the kernels mask what lies
    outside the history), and the taps' block."""
    block_t, block_c = blocks
    per, halos = block_t // HALO, t // HALO
    return (pl.BlockSpec((1, block_t, block_c), lambda b, c, i: (b, i, c)),
            pl.BlockSpec((1, HALO, block_c),
                         lambda b, c, i: (b, jnp.maximum(i * per - 1, 0), c)),
            pl.BlockSpec((1, HALO, block_c),
                         lambda b, c, i: (b, jnp.minimum((i + 1) * per,
                                                         halos - 1), c)),
            pl.BlockSpec((kernel, block_c), lambda b, c, i: (0, c)))


def _forward(x, taps, head_dim, scale, out_dtype, blocks, interpret):
    (bs, t, channels), kernel = x.shape, taps.shape[0]
    block, before, _, row = _specs(t, kernel, blocks)
    return pl.pallas_call(
        functools.partial(_forward_kernel, head_dim=head_dim, scale=scale),
        grid=(bs, channels // blocks[1], t // blocks[0]),
        in_specs=[block, before, row],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret)(x, x, taps)


def _backward(x, taps, dy, head_dim, scale, blocks, interpret):
    """``dx`` in ``x``'s dtype; the taps' gradient a history, (batch,
    kernel, channels) float32."""
    (bs, t, channels), kernel = x.shape, taps.shape[0]
    block, before, after, row = _specs(t, kernel, blocks)
    return pl.pallas_call(
        functools.partial(_backward_kernel, head_dim=head_dim, scale=scale),
        grid=(bs, channels // blocks[1], t // blocks[0]),
        in_specs=[block, before, after, block, after, row],
        out_specs=[block,
                   pl.BlockSpec((1, kernel, blocks[1]),
                                lambda b, c, i: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bs, kernel, channels), F32)],
        scratch_shapes=[pltpu.VMEM((kernel, blocks[1]), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(x, x, x, dy, dy, taps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _short_conv(x, taps, head_dim, scale, out_dtype, blocks, interpret):
    return _forward(x, taps, head_dim, scale, out_dtype, blocks, interpret)


def _short_conv_fwd(x, taps, head_dim, scale, out_dtype, blocks, interpret):
    return (_forward(x, taps, head_dim, scale, out_dtype, blocks, interpret),
            (x, taps))


def _short_conv_bwd(head_dim, scale, out_dtype, blocks, interpret,
                    residuals, dy):
    x, taps = residuals
    dx, dtaps = _backward(x, taps, dy, head_dim, scale, blocks, interpret)
    return dx, jnp.sum(dtaps, axis=0)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def short_conv(x, taps, head_dim=None, scale=1.0, out_dtype=jnp.bfloat16):
    """``silu(conv(x))``, with ``head_dim`` each head l2-normed and times
    ``scale``, in ``out_dtype``: ``x`` (batch, T, channels) as the
    projection gives it, ``taps`` (kernel, channels) float32. The kernels
    where :func:`conv_blocks` finds the shapes a block (compiled on a
    TPU, interpreted elsewhere), :func:`short_conv_plain` elsewhere."""
    (_, t, channels), kernel = x.shape, taps.shape[0]
    blocks = conv_blocks(t, channels, head_dim, kernel)
    if blocks is None:
        return short_conv_plain(x, taps, None, head_dim, scale, out_dtype)
    return _short_conv(x, taps, head_dim, float(scale), jnp.dtype(out_dtype),
                       blocks, ops.interpret())
