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

Each megablox call takes its own tiling, chosen in Python when the call
is traced, from its static shapes alone (:func:`tiling`): no option, no
search on the device, one kernel a shape.
"""

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _tgmm

from persia_tpu import metrics, ops

# what a tiling's blocks may take of the 16 MiB of VMEM a Pallas call is
# scoped to; the rest is the compiler's own scratch
VMEM_BUDGET = 14 * 2**20
# a tile's contraction and columns are whole lanes of 128, or a whole
# dimension
LANE = 128
# the rows a tile takes: every group boundary inside a tile costs a visit
# of the whole tile, and every visit a fixed step of the grid
ROWS = 256


def vmem_bytes(tm, tk, tn, outer, itemsize):
    """VMEM a tiling's blocks take: the two operands' blocks and the
    output's, each twice for the pipeline, and the float32 accumulator
    the size of the output block (``outer``: ``tgmm``, whose output block
    is (tk, tn); else ``gmm``, whose is (tm, tn))."""
    out = tk * tn if outer else tm * tn
    ins = tm * tk + (tm * tn if outer else tk * tn)
    return 2 * itemsize * (ins + out) + 4 * out


def _widths(d):
    """Tile widths for a dimension of ``d``, in order of preference: those
    that pad nothing (``d`` itself, then its divisors in whole lanes,
    widest first), then other whole lanes by fewest tiles and, for as
    many tiles, least padding."""
    lanes = range(LANE, d, LANE)
    rest = sorted((t for t in lanes if d % t), key=lambda t: (-(-d // t), t))
    return [d, *(t for t in reversed(lanes) if d % t == 0), *rest]


def fill(k, n, tiling):
    """k x n over the tile grid's k x n: the share of the walked tiles'
    area the product needs."""
    _, tk, tn = tiling
    return k * n / (-(-k // tk) * tk * -(-n // tn) * tn)


def tiling(m, k, n, outer=False, itemsize=2, rows=None):
    """``(tm, tk, tn)`` for one megablox call over ``m`` rows, contraction
    ``k`` and ``n`` columns in operands of ``itemsize`` bytes
    (``outer``: ``tgmm``, whose output is (groups, k, n); else ``gmm``,
    plain or with the matrices transposed, which walk the same blocks).
    ``tm`` is ``rows`` (``ROWS``; all of ``m`` where that is fewer), the
    buffer padded to whole tiles. tk and tn are whole lanes or a whole
    dimension, in the order :func:`_widths` prefers, with blocks that fit
    ``VMEM_BUDGET``: ``gmm`` takes the first tk for which a tn fits (all
    of k, where it does: one matrix block a group and column tile, which
    stays put while the group's row tiles pass), then the first tn;
    ``tgmm``, whose output block sits in VMEM, the (tk, tn) that pads
    nothing in the fewest tiles, the wider tk first (where none pads
    nothing, the fewest tiles, then the least padding). Sets the gauge
    ``grouped_matmul_tile_fill``: the least :func:`fill` of the calls
    built in the process."""
    tm = min(rows or ROWS, m)
    pairs = [(tk, tn) for tk in _widths(k) for tn in _widths(n)
             if vmem_bytes(tm, tk, tn, outer, itemsize) <= VMEM_BUDGET]
    if not pairs:
        raise ValueError(f"no tiling of ({m}, {k}, {n}) fits VMEM")
    if outer:
        tk, tn = min(pairs, key=lambda p: (
            fill(k, n, (tm, *p)) < 1, -(-k // p[0]) * -(-n // p[1]),
            -fill(k, n, (tm, *p)), -p[0]))
    else:
        tk = min((p[0] for p in pairs), key=_widths(k).index)
        tn = min((p[1] for p in pairs if p[0] == tk), key=_widths(n).index)
    chosen = (tm, tk, tn)
    gauge = metrics.default_registry().gauge("grouped_matmul_tile_fill")
    share = fill(k, n, chosen)
    gauge.set(min(share, gauge.value or share))
    return chosen


def _padded(tm, group_sizes, *rows):
    """``rows`` padded to whole tiles of ``tm``, the padding counted in
    the trailing group, which no tile visits."""
    pad = -rows[0].shape[0] % tm
    if pad:
        group_sizes = group_sizes.at[-1].add(pad)
        rows = [jnp.pad(x, ((0, pad), (0, 0))) for x in rows]
    return group_sizes, *rows


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (m, k) sorted by group; ``rhs`` (held, k, n);
    ``group_sizes`` (held + 1,) int32, summing to m. Returns (m, n) in
    ``lhs``'s dtype, float32 accumulation inside. Compiled on a TPU, run
    by the Pallas interpreter elsewhere (the CPU tests)."""
    (m, k), n = lhs.shape, rhs.shape[2]
    tiles = tiling(m, k, n, itemsize=lhs.dtype.itemsize)
    group_sizes, lhs = _padded(tiles[0], group_sizes, lhs)
    out = _gmm(lhs, rhs, group_sizes, lhs.dtype, tiles,
               interpret=ops.interpret())
    return out[:m] if out.shape[0] > m else out


def grouped_matmul_pullback(lhs, rhs, group_sizes, grad):
    """``grad`` (m, n), the cotangent of ``grouped_matmul(lhs, rhs,
    group_sizes)``, carried back to ``lhs`` and to ``rhs``, each in its
    dtype: the two calls megablox's own derivative makes, the product
    with the matrices transposed (contraction n, columns k) and the
    grouped outer product (output (held, k, n)), each at its own tiles.
    For a caller that writes its derivative out (a loop whose trip count
    is read on the device has none of JAX's making)."""
    interpret = ops.interpret()
    (m, k), (held, _, n) = lhs.shape, rhs.shape
    to_lhs_tiles = tiling(m, n, k, itemsize=grad.dtype.itemsize)
    sizes, padded = _padded(to_lhs_tiles[0], group_sizes, grad)
    to_lhs = _gmm(padded, rhs, sizes, lhs.dtype, to_lhs_tiles,
                  transpose_rhs=True, interpret=interpret)
    to_rhs_tiles = tiling(m, k, n, outer=True, itemsize=lhs.dtype.itemsize)
    sizes, lhs, grad = _padded(to_rhs_tiles[0], group_sizes, lhs, grad)
    to_rhs = _tgmm(lhs.swapaxes(0, 1), grad, sizes, rhs.dtype,
                   to_rhs_tiles, None, held, interpret=interpret)
    return (to_lhs[:m] if to_lhs.shape[0] > m else to_lhs), to_rhs
