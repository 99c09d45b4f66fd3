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


# rows, contraction and output columns of a tile; never tuned on a chip
TILING = (512, 512, 512)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (m, k) sorted by group; ``rhs`` (held, k, n);
    ``group_sizes`` (held + 1,) int32, summing to m. Returns (m, n) in
    ``lhs``'s dtype, float32 accumulation inside. Compiled on a TPU, run
    by the Pallas interpreter elsewhere (the CPU tests)."""
    interpret = jax.default_backend() != "tpu"
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = (min(t, d) for t, d in zip(TILING, (m, k, n)))
    pad = -m % tm
    if pad:     # rows of the trailing group, which no tile visits
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    out = _gmm(lhs, rhs, group_sizes, lhs.dtype, (tm, tk, tn), None, None,
               False, interpret)
    return out[:m] if pad else out
