"""The grouped product (``ops/grouped_matmul.py``): the tiling each of
megablox's three calls is given at the sequence cells' shapes, and the
interpreted calls against a plain float32 product a group."""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu import metrics
from persia_tpu.models.hybrid_seq import ACTIVATIONS
from persia_tpu.ops import grouped_matmul
from persia_tpu.ops.grouped_matmul import (
    grouped_matmul_pullback,
    tiling,
)

from tools.kernel_timing import expert_layers

F32, BF16 = jnp.float32, jnp.bfloat16
PARENT = (512, 512, 512)    # the one tiling every call took before

# pair-buffer rows, hidden width, expert width, the activation's factor
# on the first product's columns and held experts of each sequence
# cell's expert layers, as the benchmark's cells run them
CELLS = {name: (rows, hidden, width, ACTIVATIONS[activation].wider, held)
         for name, (_, rows, hidden, width, activation, _, _, held)
         in expert_layers().items()}


def cell_calls(name):
    """(rows, contraction, columns, outer) of the six megablox calls an
    expert layer makes: each product forward, the product with its
    matrix transposed, and the grouped outer product."""
    rows, hidden, width, wider, _ = CELLS[name]
    first = wider * width
    return {"w1": (rows, hidden, first, False),
            "w2": (rows, width, hidden, False),
            "w1_t": (rows, first, hidden, False),
            "w2_t": (rows, hidden, width, False),
            "w1_outer": (rows, hidden, first, True),
            "w2_outer": (rows, width, hidden, True)}


CALLS = list(itertools.product(sorted(CELLS), ("w1", "w2", "w1_t", "w2_t",
                                               "w1_outer", "w2_outer")))


def test_the_cells_are_the_six_sequence_cells():
    assert len(CELLS) == 6
    assert CELLS["mellum2-12b-a2.5b"] == (16384, 2304, 896, 2, 8)
    assert CELLS["nemotron-3-nano-30b-a3b"] == (8192, 2688, 1856, 1, 8)


@pytest.mark.parametrize("cell,call", CALLS)
def test_the_tiling_is_whole_lanes_and_whole_row_tiles(cell, call):
    """Whether the blocks fit VMEM the compiler says
    (``tests/test_tpu_lowering.py -k grouped``)."""
    m, k, n, outer = cell_calls(cell)[call]
    tm, tk, tn = tiling(m, k, n, outer)
    assert tm == grouped_matmul.ROWS
    for t, d in ((tk, k), (tn, n)):
        assert t == d or (t % 128 == 0 and t < d), (t, d)
    assert m % tm == 0     # the buffer is whole tiles: nothing padded


@pytest.mark.parametrize("cell,call", CALLS)
def test_the_tiling_fills_no_less_than_the_parents(cell, call):
    """Where k and n are whole lanes a tiling pads neither, and where a
    width is not (nemotron's experts of 1856) it pads less than the
    parent's (512, 512, 512), which walked up to a quarter more than the
    product."""
    m, k, n, outer = cell_calls(cell)[call]
    chosen = grouped_matmul.fill(k, n, tiling(m, k, n, outer))
    parent = grouped_matmul.fill(k, n, tuple(min(t, d) for t, d in
                                             zip(PARENT, (m, k, n))))
    assert chosen >= parent
    if k % 128 == 0 and n % 128 == 0:
        assert chosen == 1.0


def test_the_contraction_is_whole_where_it_fits():
    """``gmm`` keeps the whole contraction in one block, so a group's
    matrix block stays put while its row tiles pass; where the columns
    are not whole lanes it takes the fewest column tiles that fit, each
    as narrow as that count allows."""
    m, k, n, _ = cell_calls("nemotron-3-nano-30b-a3b")["w1"]
    assert tiling(m, k, n) == (256, 2688, 640)
    # 10 000 columns have no divisor in whole lanes: 20 tiles of 512
    assert tiling(16384, 4096, 10000) == (256, 4096, 512)


def test_a_small_call_takes_its_whole_dimensions():
    """A rehearsal's shapes: every tile is the whole dimension, the rows'
    too where they are fewer than ``ROWS``."""
    assert tiling(100, 64, 96) == (100, 64, 96)
    assert tiling(100, 64, 96, outer=True) == (100, 64, 96)


def test_the_gauge_keeps_the_least_fill_of_the_calls_built():
    gauge = metrics.default_registry().gauge("grouped_matmul_tile_fill")
    gauge.set(0)
    tiling(*cell_calls("mellum2-12b-a2.5b")["w1"])
    assert gauge.value == 1.0
    m, k, n = 16384, 4096, 10000
    chosen = tiling(m, k, n)
    assert gauge.value == pytest.approx(grouped_matmul.fill(k, n, chosen))
    assert gauge.value < 1.0
    tiling(*cell_calls("mellum2-12b-a2.5b")["w2"])
    assert gauge.value < 1.0      # the least, not the last


def _plain(lhs, rhs, sizes):
    """The product a group in float32: rows past the held groups zero."""
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    ends = np.cumsum(np.asarray(sizes)[:-1])
    for g, (lo, hi) in enumerate(zip(np.concatenate([[0], ends]), ends)):
        out[lo:hi] = lhs[lo:hi] @ rhs[g]
    return out


def _plain_pullback(lhs, rhs, sizes, grad):
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    grad = np.asarray(grad, np.float32)
    to_lhs, to_rhs = np.zeros_like(lhs), np.zeros_like(rhs)
    ends = np.cumsum(np.asarray(sizes)[:-1])
    for g, (lo, hi) in enumerate(zip(np.concatenate([[0], ends]), ends)):
        to_lhs[lo:hi] = grad[lo:hi] @ rhs[g].T
        to_rhs[g] = lhs[lo:hi].T @ grad[lo:hi]
    return to_lhs, to_rhs


# rows, contraction, expert width, the activation's factor, group sizes
# (the last the trailing rows of no held expert): a rehearsal's widths
# for both activations, an empty group, no trailing rows, and rows that
# are not whole tiles (1100: the buffer is padded to tiles of lanes)
SMALL = {"relu2": (256, 256, 128, 1, [40, 70, 0, 90, 56]),
         "swiglu": (256, 256, 128, 2, [100, 0, 60, 30, 66]),
         "no_trailing": (128, 128, 128, 2, [30, 50, 48, 0]),
         "ragged_rows": (1100, 128, 128, 1, [300, 0, 500, 300])}


def _operands(case, dtype, seed=0):
    m, k, width, wider, sizes = SMALL[case]
    rng = np.random.default_rng(seed)
    held = len(sizes) - 1
    return (jnp.asarray(rng.normal(size=(m, k)), dtype),
            jnp.asarray(rng.normal(size=(held, k, wider * width))
                        / np.sqrt(k), dtype),
            jnp.asarray(sizes, jnp.int32),
            jnp.asarray(rng.normal(size=(m, wider * width)), dtype))


@pytest.mark.parametrize("dtype,tol", [(F32, 1e-5), (BF16, 1e-2)])
@pytest.mark.parametrize("case", sorted(SMALL))
def test_the_interpreted_calls_equal_a_plain_product_a_group(case, dtype,
                                                             tol):
    """Forward and both pullbacks, interpreted at the tiles the rule
    gives, against float32 numpy on the same (rounded) operands: rows of
    an empty group or past the held groups come out zero, and an empty
    group's matrix gradient is zero."""
    lhs, rhs, sizes, grad = _operands(case, dtype)
    out = grouped_matmul.grouped_matmul(lhs, rhs, sizes)
    to_lhs, to_rhs = grouped_matmul_pullback(lhs, rhs, sizes, grad)
    want = [_plain(lhs, rhs, sizes), *_plain_pullback(lhs, rhs, sizes, grad)]
    for got, ref in zip((out, to_lhs, to_rhs), want):
        assert got.dtype == dtype and got.shape == ref.shape
        got = np.asarray(got, np.float32)
        np.testing.assert_allclose(got, ref, rtol=tol,
                                   atol=tol * np.abs(ref).max())
        held_rows = int(np.sum(np.asarray(sizes)[:-1]))
        if got.shape[0] == lhs.shape[0]:
            assert not np.any(got[held_rows:])
    for g in np.flatnonzero(np.asarray(sizes)[:-1] == 0):
        assert not np.any(np.asarray(to_rhs[g], np.float32))


def test_tiles_smaller_than_the_operands_give_the_same_numbers(monkeypatch):
    """A budget that holds no whole operand makes every call walk several
    tiles in rows, contraction and columns, the pullback's two calls at
    tiles of their own: the numbers are the plain ones all the same."""
    monkeypatch.setattr(grouped_matmul, "VMEM_BUDGET", 2**19)
    monkeypatch.setattr(grouped_matmul, "ROWS", 128)
    lhs, rhs, sizes, grad = _operands("swiglu", F32)
    m, k = lhs.shape
    n = rhs.shape[2]
    tiles = [tiling(m, k, n, itemsize=4), tiling(m, n, k, itemsize=4),
             tiling(m, k, n, outer=True, itemsize=4)]
    assert tiles == [(128, 128, 128)] * 3
    out = grouped_matmul.grouped_matmul(lhs, rhs, sizes)
    to_lhs, to_rhs = grouped_matmul_pullback(lhs, rhs, sizes, grad)
    want = [_plain(lhs, rhs, sizes), *_plain_pullback(lhs, rhs, sizes, grad)]
    for got, ref in zip((out, to_lhs, to_rhs), want):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5,
                                   atol=1e-5 * np.abs(ref).max())
