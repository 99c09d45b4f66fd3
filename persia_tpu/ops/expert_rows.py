"""Pallas kernels that move an expert layer's pair rows.

``models/hybrid_seq.dispatch_pairs`` takes a batch's held (token,
expert) pairs through their experts a buffer of ``rows`` pairs at a
time, sorted by expert. Around the grouped product two movements touch
every row of the buffer: the token rows gathered into it, and the
product's rows, weighted, summed back onto their tokens (forward; the
backward gathers the cotangent's rows and sums the first product's
pullback back the same way). In ``jax.numpy`` they are a gather and a
scatter-add over the whole buffer, whose rows past the held groups are
padding. Here each is a kernel that reads how many rows of the buffer
hold pairs (the group sizes, on the device) and stops there:

``take_rows(source, token_of, count)`` -> ``(rows, hidden)``
    row ``r`` is ``source[token_of[r]]`` for ``r < count``, zero after,
    copied exactly in ``source``'s dtype (the token rows in bfloat16,
    the cotangent's in float32).
``add_rows(acc, rows, weight, token_of, count)`` -> ``acc``
    ``acc[token_of[r]] += rows[r] * weight[r]`` for ``r < count``, in
    float32, one row after the other in the buffer's order: the sums of
    the scatter-add, with no other order of addition.

Both hold a column slab of every token's row in VMEM (``take_rows``
the source, widened to float32 once where it is bfloat16; ``add_rows``
the sums, read once and written back once a slab) and walk the buffer
a block of ``BLOCK`` rows at a time (the grid), a block's rows one after
the other (``lax.fori_loop``): a row is a dynamic sublane load and store
in VMEM, where a DMA of one row of a (tokens, hidden) array, which HBM
tiles by eight rows, is refused by the chip's compiler. Blocks past the
held rows fetch nothing.

**One trace and one lowering a shape.** A ``pallas_call`` has no
lowering cache of its own: a step whose eight expert layers and their
backwards each called the kernels bare would trace and Mosaic-lower
every occurrence again, seconds of a step's first call (PERF.md §6).
Each entry here is a module-level ``jax.jit`` with its shape parameters
static, so all of them are calls of one traced and one lowered callee a
shape (``tests/test_expert_rows.py`` holds a tower's gradient to it).
No kernel body loops over rows in Python, so a lowered kernel's size
does not grow with the buffer's.

:class:`PairRows` chooses by the shapes alone: the kernels where the
hidden width is whole lane tiles and the tokens and the buffer whole
tiles of sixteen rows (every sequence cell), ``jnp.take`` and
``.at[].add`` elsewhere (the rehearsal sizes). On the CPU the kernels
run interpreted.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from persia_tpu import ops

F32 = jnp.float32
_LANES, _SUBLANES = 128, 16

# pair rows a grid step, and the widest column slab of every token's row
# held in VMEM (8192 tokens x 1152 columns: 37.7 MB in float32)
BLOCK = 256
SLAB = 1152
VMEM_LIMIT = 100 * 1024 * 1024


def _block(rows):
    """The largest power of two up to ``BLOCK`` that divides ``rows``."""
    block = BLOCK
    while rows % block:
        block //= 2
    return block


def _slab(hidden, tokens):
    """The widest multiple of a lane tile that divides ``hidden``, up to
    ``SLAB`` columns and 80 % of ``VMEM_LIMIT`` for eight bytes a token
    and column: two pipeline buffers of a float32 slab, or of a bfloat16
    one and its float32 widening."""
    most = min(SLAB, VMEM_LIMIT * 4 // 5 // (8 * tokens) // _LANES * _LANES)
    return max(c for c in range(_LANES, max(most, _LANES) + 1, _LANES)
               if hidden % c == 0)


def _live(b, count_ref, block):
    """A buffer block's index: the block itself up to the last one that
    holds a pair, that one after it (nothing fetched past the pairs)."""
    return jnp.minimum(b, jnp.maximum(pl.cdiv(count_ref[0], block) - 1, 0))


def _take_kernel(count_ref, token_ref, source_ref, out_ref, buf, *wide):
    block = out_ref.shape[0]
    base = pl.program_id(1) * block
    here = jnp.clip(count_ref[0] - base, 0, block)
    rows_ref = source_ref
    if wide:
        # a row of a narrower dtype is no whole sublane: its slab is
        # widened to float32 in VMEM once, at the first block
        rows_ref, = wide
        chunk = _block(rows_ref.shape[0])

        @pl.when(pl.program_id(1) == 0)
        def _():
            def widen(i, carry):
                part = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
                rows_ref[part, :] = source_ref[part, :].astype(F32)
                return carry

            lax.fori_loop(0, rows_ref.shape[0] // chunk, widen, 0)

    def one(r, carry):
        buf[pl.ds(r, 1), :] = rows_ref[pl.ds(token_ref[base + r], 1), :]
        return carry

    lax.fori_loop(0, here, one, 0)
    live = lax.broadcasted_iota(jnp.int32, out_ref.shape, 0) < here
    out_ref[...] = jnp.where(live, buf[...], 0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def take_rows(source, token_of, count, *, interpret):
    """``source`` (tokens, hidden), ``token_of`` (rows,) int32, ``count``
    (1,) int32 -> (rows, hidden) in ``source``'s dtype: row ``r`` is
    ``source[token_of[r]]`` for ``r < count``, zero after."""
    tokens, hidden = source.shape
    rows = token_of.shape[0]
    block, slab = _block(rows), _slab(hidden, tokens)
    scratch = [pltpu.VMEM((block, slab), F32)]
    if source.dtype != F32:
        scratch.append(pltpu.VMEM((tokens, slab), F32))
    with jax.named_scope("expert_rows"):
        return pl.pallas_call(
            _take_kernel, name="expert_rows",
            out_shape=jax.ShapeDtypeStruct((rows, hidden), source.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(hidden // slab, rows // block),
                in_specs=[pl.BlockSpec((tokens, slab),
                                       lambda c, b, *_: (0, c))],
                out_specs=pl.BlockSpec((block, slab),
                                       lambda c, b, *_: (b, c)),
                scratch_shapes=scratch),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(count, token_of, source)


def _add_kernel(count_ref, token_ref, rows_ref, weight_ref, acc_in, acc_ref,
                scaled, sem):
    block, slab = rows_ref.shape
    c, b = pl.program_id(0), pl.program_id(1)
    base = b * block
    here = jnp.clip(count_ref[0] - base, 0, block)

    @pl.when(b == 0)
    def _():
        copy = pltpu.make_async_copy(
            acc_in.at[:, pl.ds(pl.multiple_of(c * slab, _LANES), slab)],
            acc_ref, sem)
        copy.start()
        copy.wait()

    @pl.when(here > 0)
    def _():
        scaled[...] = rows_ref[...].astype(F32) * weight_ref[...]

    def one(r, carry):
        t = pl.ds(token_ref[base + r], 1)
        acc_ref[t, :] = acc_ref[t, :] + scaled[pl.ds(r, 1), :]
        return carry

    lax.fori_loop(0, here, one, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def add_rows(acc, rows, weight, token_of, count, *, interpret):
    """``acc`` (tokens, hidden) float32 with ``rows[r] * weight[r]``
    added onto row ``token_of[r]`` for every ``r < count``, in the
    buffer's order; ``rows`` (rows, hidden), ``weight`` (rows,)
    float32."""
    tokens, hidden = acc.shape
    n = rows.shape[0]
    block, slab = _block(n), _slab(hidden, tokens)
    with jax.named_scope("expert_rows"):
        return pl.pallas_call(
            _add_kernel, name="expert_rows",
            out_shape=jax.ShapeDtypeStruct(acc.shape, F32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(hidden // slab, n // block),
                in_specs=[
                    pl.BlockSpec((block, slab), lambda c, b, n, _: (
                        _live(b, n, block), c)),
                    pl.BlockSpec((block, 1), lambda c, b, n, _: (
                        _live(b, n, block), 0)),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tokens, slab),
                                       lambda c, b, *_: (0, c)),
                scratch_shapes=[pltpu.VMEM((block, slab), F32),
                                pltpu.SemaphoreType.DMA(())]),
            input_output_aliases={4: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(count, token_of, rows, weight.reshape(n, 1), acc)


def _count(sizes):
    """Rows of a buffer that hold a pair: all but the last group's."""
    return jnp.sum(sizes[:-1]).reshape(1).astype(jnp.int32)


class PairRows(NamedTuple):
    """How a pair buffer's rows move: the kernels where ``kernels``
    (:meth:`of` decides from the shapes), XLA's gather and scatter-add
    elsewhere."""

    kernels: bool

    @classmethod
    def of(cls, tokens, hidden, rows):
        return cls(hidden % _LANES == 0 and rows % _SUBLANES == 0
                   and tokens % _SUBLANES == 0)

    def take(self, x, token_of, sizes):
        if not self.kernels:
            return jnp.take(x, token_of, axis=0)
        return take_rows(x, token_of, _count(sizes),
                         interpret=ops.interpret())

    def add(self, acc, rows, weight, token_of, sizes):
        if not self.kernels:
            return acc.at[token_of].add(rows.astype(F32) * weight[:, None])
        return add_rows(acc, rows, weight, token_of, _count(sizes),
                        interpret=ops.interpret())
