"""Grouped matrix product for an expert layer that holds some experts.

Rows arrive sorted by the expert they were routed to; each run of rows
meets its own expert's matrix. The kernel is the grouped matmul that
ships with JAX (``jax.experimental.pallas.ops.tpu.megablox``, Pallas
forward and backward): its grid runs over the row tiles that hold rows of
a group whose matrix is here, a number read from ``group_sizes`` at run
time, so the work follows the rows routed to the held experts and not the
static size of the buffer. ``jax.lax.ragged_dot`` has the same contract
but leaves the schedule to the compiler.

``group_sizes`` has one entry more than ``rhs`` has matrices: the last
counts the trailing rows that belong to no held expert. No tile of theirs
is visited and their output rows are zero.
"""

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm_plain
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _tgmm_plain


# rows, contraction and output columns of a tile; never tuned on a chip
TILING = (512, 512, 512)


def _tiles(lhs, rhs, group_sizes):
    """The tile of a product of these shapes, the rows that fill ``lhs``
    to whole tiles, and the group sizes with those rows in the trailing
    group, which no tile visits."""
    m, k = lhs.shape
    tiling = tuple(min(t, d) for t, d in zip(TILING, (m, k, rhs.shape[2])))
    pad = -m % tiling[0]
    if pad:
        group_sizes = group_sizes.at[-1].add(pad)
    return tiling, pad, group_sizes


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (m, k) sorted by group; ``rhs`` (held, k, n);
    ``group_sizes`` (held + 1,) int32, summing to m. Returns (m, n) in
    ``lhs``'s dtype, float32 accumulation inside. Compiled on a TPU, run
    by the Pallas interpreter elsewhere (the CPU tests)."""
    interpret = jax.default_backend() != "tpu"
    m = lhs.shape[0]
    tiling, pad, group_sizes = _tiles(lhs, rhs, group_sizes)
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None,
               False, interpret)
    return out[:m] if pad else out


def grouped_matmul_pullback(lhs, rhs, group_sizes, grad):
    """``grad`` (m, n), the cotangent of ``grouped_matmul(lhs, rhs,
    group_sizes)``, carried back to ``lhs`` and to ``rhs``, each in its
    dtype: the two calls megablox's own derivative makes, the product
    with the matrices transposed and the grouped outer product, at the
    forward's tiles. For a caller that writes its derivative out (a loop
    whose trip count is read on the device has none of JAX's making)."""
    interpret = jax.default_backend() != "tpu"
    m = lhs.shape[0]
    tiling, pad, group_sizes = _tiles(lhs, rhs, group_sizes)
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        grad = jnp.pad(grad, ((0, pad), (0, 0)))
    to_lhs = _gmm_plain(grad, rhs, group_sizes, lhs.dtype, tiling,
                        transpose_rhs=True, interpret=interpret)
    to_rhs = _tgmm_plain(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                         tiling, None, rhs.shape[0], interpret=interpret)
    return (to_lhs[:m] if pad else to_lhs), to_rhs
