"""The attention kernel must lower and compile for TPU — checked on the
CPU, with no chip.

Interpret mode accepts block shapes the TPU lowering refuses, which is
how 20 PRs of green CPU tests hid a kernel that could neither train nor
take a mask on the chip. Two guards, both run here on the CPU:

- cross-lowering with ``jax.export`` (platforms=["tpu"]): runs the
  Pallas TPU lowering (block-shape rules, Mosaic MLIR emission);
- ahead-of-time compilation against a compile-only v5e topology, in a
  subprocess: runs libtpu's compiler, Mosaic included (VMEM budget,
  unsupported layout changes). Skipped only where libtpu is not
  installed; with libtpu, any failure to get the topology fails.

The hybrid sequence tower's ``K`` layer (Kimi Delta Attention, whose
recurrence is ``ops/kda_scan``'s two Pallas kernels) goes through the
same two guards at the published widths, forward and backward under the
tower's ``nn.remat``; so do the two kernels alone at the shape the
delta-rule cell of the benchmark runs a layer (1 x 8192 x 32 x 128,
chunk 64).

The short convolutions (``ops/short_conv``'s two kernels: causal taps,
SiLU and the l2 norm a head) go through both guards alone at the
delta-rule cell's query (1 x 8192 x 4096, heads of 128) and the
state-space cell's stream width (1 x 8192 x 6144, no norm), and inside
the ``K`` layer over a history of whole blocks.

The ``S`` layer (attention over the keys a learned indexer selects:
the flash kernels handed an int8 selection block a scheduled pair, the
index scores and their pullback ``ops/sparse_select``'s two kernels
where the history is whole tiles and whole key blocks, plain XLA by
tiles elsewhere) goes through the same two guards at the published
widths (hidden 2048, 32 / 4 heads of 128, 16 index heads of 64, 2048
keys a query), and so do ``flash_attention_selected`` alone at the
selected-attention cell's shape (1 x 32 x 8192 x 128) and the index
scores' kernels at the cell's (16 x 64, a tile of 512 queries against
8192 keys, bfloat16, the tile's position traced).

The expert layers' pair-row kernels (``ops/expert_rows``: the token
rows gathered into a pass's buffer in bfloat16, the cotangent's in
float32, and the rows summed back onto the tokens) go through both
guards alone at each sequence cell's tokens, hidden width and buffer.

So do the expert layers' grouped products (``ops/grouped_matmul``:
megablox's product, its transposed product and its grouped outer
product, for both matrices) at each sequence cell's buffer, widths and
held experts, at the tiling each call is given there: a tiling whose
blocks overflow the VMEM a call is scoped to is refused here.

Neither replaces the compiled-and-compared check on the chip
(``chip_smoke.py`` kernel phase, ``test_compiled_on_tpu``): values only
come from a run.
"""

import importlib.util
import itertools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

if __name__ == "__main__":  # the AOT subprocess: no conftest on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from persia_tpu.ops.flash_attention import (  # noqa: E402
    _clamp_block,
    flash_attention_masked,
)
from test_grouped_matmul import CELLS as EXPERT_SHAPES  # noqa: E402

# (T, dk, dv, dtype), key width and value width: the smoke's two lengths
# at the full head width, the latent attention towers' history at a
# 256-wide head and at keys of 192 beside values of 128, plus small
# ragged shapes the CPU tests use (one of them unequal)
SHAPES = [
    (4096, 128, 128, jnp.bfloat16),
    (1000, 128, 128, jnp.bfloat16),
    (8192, 256, 256, jnp.bfloat16),
    (8192, 192, 128, jnp.bfloat16),
    (200, 64, 64, jnp.bfloat16),
    (100, 8, 8, jnp.float32),
    (200, 24, 16, jnp.bfloat16),
]
AOT_SHAPES = SHAPES[:4]
VARIANTS = list(itertools.product([False, True], [False, True]))  # mask×grad


def _attn_fn(masked: bool, grad: bool, causal: bool = False):
    def fwd(q, k, v, m):
        return flash_attention_masked(
            q, k, v, kv_mask=m if masked else None, causal=causal,
            interpret=False)

    if not grad:
        return fwd

    def bwd(q, k, v, m):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, m).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    return bwd


def _avals(t, dk, dv, dtype, sharding=None):
    q = jax.ShapeDtypeStruct((4, 8, t, dk), dtype, sharding=sharding)
    v = jax.ShapeDtypeStruct((4, 8, t, dv), dtype, sharding=sharding)
    m = jax.ShapeDtypeStruct((4, t), jnp.bool_, sharding=sharding)
    return q, q, v, m


@pytest.mark.parametrize("masked,grad", VARIANTS)
@pytest.mark.parametrize("t,dk,dv,dtype", SHAPES)
def test_attention_cross_lowers_for_tpu(t, dk, dv, dtype, masked, grad):
    exported = jax.export.export(
        jax.jit(_attn_fn(masked, grad)), platforms=["tpu"])(
            *_avals(t, dk, dv, dtype))
    assert "tpu_custom_call" in exported.mlir_module()


def test_block_requests_clamp_to_the_lane_width():
    """Ulysses passes chunk_size as the block. A request is clamped to
    the sequence length and rounded UP to a multiple of 128, so a
    sub-128 block does not exist on this kernel: asking for one gets
    128."""
    assert [_clamp_block(b, 300) for b in (32, 64, 128, 200, 512)] == [
        128, 128, 128, 256, 384]
    assert _clamp_block(512, 4096) == 512
    assert _clamp_block(512, 1000) == 512   # T is padded to 1024
    assert _clamp_block(512, 100) == 128    # T is padded to 128


def test_custom_block_sizes_lower_for_tpu():
    """The distinct blocks the clamp can yield at T=300 (128, 256, 384)
    each lower, backward included."""
    for block in (128, 200, 512):
        def f(q, k, v, m, block=block):
            return jax.grad(lambda q: flash_attention_masked(
                q, k, v, kv_mask=m, block_q=block, block_k=block,
                interpret=False).astype(jnp.float32).sum())(q)

        exported = jax.export.export(jax.jit(f), platforms=["tpu"])(
            *_avals(300, 128, 128, jnp.bfloat16))
        assert "tpu_custom_call" in exported.mlir_module()


def _delta_layer(t):
    """The gradient of a ``K`` layer of the hybrid sequence tower at the
    published widths (hidden 2304, 32 heads of 128, chunk 64) over ``t``
    positions, under the tower's ``nn.remat``, and its abstract
    arguments."""
    from flax import linen as nn

    from persia_tpu.models import hybrid_seq

    def layer(of=hybrid_seq._Layer):
        return of(hybrid_seq.DeltaAttention(parent=None), "kda_attention",
                  1e-5, jnp.bfloat16)

    h = jax.ShapeDtypeStruct((1, t, 2304), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer().init(
        jax.random.key(0), jnp.zeros((1, 64, 2304), jnp.bfloat16)))

    from persia_tpu.ops.kda_scan import RESIDUAL_NAMES

    remat = nn.remat(
        hybrid_seq._Layer,
        policy=jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES))

    def grad(params, h):
        return jax.grad(lambda p, h: jnp.sum(
            layer(remat).apply(p, h).astype(jnp.float32)),
            argnums=(0, 1))(params, h)

    return grad, params, h


def _delta_kernels(grad, sharding=None):
    """``kda_scan`` compiled (not interpreted), or the gradient of all
    its five inputs, at the delta-rule cell's shape a layer, and its
    abstract arguments."""
    from persia_tpu.ops.kda_scan import kda_scan

    def op(*xs):
        return kda_scan(*xs, chunk=64, interpret=False)

    def gradient(*xs):
        return jax.grad(lambda *ys: jnp.sum(op(*ys)),
                        argnums=(0, 1, 2, 3, 4))(*xs)

    wide = (1, 8192, 32, 128)
    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for shape, dtype in [(wide, jnp.bfloat16)] * 3
             + [(wide, jnp.float32), (wide[:3], jnp.float32)]]
    return (gradient if grad else op), avals


def test_the_delta_rule_layer_cross_lowers_for_tpu(monkeypatch):
    # the mixer's op asks the default backend whether to compile or to
    # interpret, and here that is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    grad, params, h = _delta_layer(1000)        # pads to whole chunks
    exported = jax.export.export(jax.jit(grad), platforms=["tpu"])(params, h)
    text = exported.mlir_module()
    # the recurrence is kernels: one forward, kept across nn.remat, and
    # one backward, and no loop of XLA's carries a state
    assert text.count("tpu_custom_call") == 2
    assert "stablehlo.while" not in text


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_the_delta_rule_kernels_cross_lower_at_the_cell_s_shape(grad):
    fn, avals = _delta_kernels(grad)
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *avals).mlir_module()
    assert text.count("tpu_custom_call") == 1 + grad


# (channels, head width) of the two cells' convolutions: the delta-rule
# cell's query, normed and scaled, and the state-space cell's stream
# width, with no norm
CONV_SHAPES = {"kimi_query": (4096, 128), "nemotron_stream": (6144, None)}


def _conv_kernels(name, grad, sharding=None):
    """``short_conv`` at a cell's shape, or its pullback to ``x`` and the
    taps, and its abstract arguments; compiled where the default backend
    reads ``tpu``."""
    from persia_tpu.ops.short_conv import short_conv

    channels, head_dim = CONV_SHAPES[name]

    def op(x, taps):
        return short_conv(x, taps, head_dim,
                          128 ** -0.5 if head_dim else 1.0)

    def gradient(x, taps, dy):
        return jax.vjp(op, x, taps)[1](dy)

    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for shape, dtype in (((1, 8192, channels), jnp.bfloat16),
                                  ((4, channels), jnp.float32),
                                  ((1, 8192, channels), jnp.bfloat16))]
    return (gradient, avals) if grad else (op, avals[:2])


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
@pytest.mark.parametrize("name", sorted(CONV_SHAPES))
def test_the_conv_kernels_cross_lower_at_the_cells_shapes(monkeypatch, name,
                                                          grad):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, avals = _conv_kernels(name, grad)
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *avals).mlir_module()
    # the pullback rebuilds the pre-activation itself: one call either way
    assert text.count("tpu_custom_call") == 1


def test_the_delta_rule_layer_s_convolutions_are_kernels(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    grad, params, h = _delta_layer(1024)        # whole blocks of 512
    text = jax.export.export(jax.jit(grad), platforms=["tpu"])(
        params, h).mlir_module()
    # the recurrence's two calls; three convolutions forward, again in
    # the rebuilt layer (nothing of theirs is kept), and three backward
    assert text.count("tpu_custom_call") == 2 + 3 * 3


def _selected_layer(t):
    """The gradient of an ``S`` layer of the hybrid sequence tower at the
    published widths over ``t`` positions, its output and its alignment
    loss both, under the tower's ``nn.remat`` and its policy, and its
    abstract arguments."""
    from flax import linen as nn

    from persia_tpu.models import hybrid_seq
    from persia_tpu.ops.flash_attention import RESIDUAL_NAMES

    def layer(of=hybrid_seq._Layer):
        return of(hybrid_seq.SelectedAttention(parent=None),
                  "selected_attention", 1e-6, jnp.bfloat16)

    h = jax.ShapeDtypeStruct((1, t, 2048), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer().init(
        jax.random.key(0), jnp.zeros((1, 64, 2048), jnp.bfloat16)))
    remat = nn.remat(
        hybrid_seq._Layer,
        policy=jax.checkpoint_policies.save_only_these_names(
            *RESIDUAL_NAMES, *hybrid_seq.SELECT_RESIDUAL_NAMES))

    def grad(params, h):
        def scalar(p, h):
            out, loss = layer(remat).apply(p, h)
            return jnp.sum(out.astype(jnp.float32)) + loss
        return jax.grad(scalar, argnums=(0, 1))(params, h)

    return grad, params, h


def _selected_kernels(grad, sharding=None):
    """``flash_attention_selected`` compiled (not interpreted), or the
    gradient of its three inputs, at the selected-attention cell's shape
    a layer, and its abstract arguments."""
    from persia_tpu.ops.flash_attention import flash_attention_selected

    def op(q, k, v, select):
        return flash_attention_selected(q, k, v, select, interpret=False)

    def gradient(q, k, v, select):
        return jax.grad(lambda *xs: jnp.sum(
            op(*xs, select)[0].astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    wide = (1, 32, 8192, 128)
    avals = [jax.ShapeDtypeStruct(wide, jnp.bfloat16, sharding=sharding)
             for _ in range(3)]
    avals.append(jax.ShapeDtypeStruct((1, 8192, 8192), jnp.int8,
                                      sharding=sharding))
    return (gradient if grad else op), avals


def _index_kernels(grad, sharding=None):
    """``sparse_select.index_scores`` compiled (not interpreted) over a
    tile at a traced position, or its pullback to all three inputs, at
    the selected-attention cell's shape a tile, and its abstract
    arguments."""
    from persia_tpu.ops import sparse_select

    def op(q_t, k_i, w_t, start):
        return sparse_select.index_scores(q_t, k_i, w_t, start,
                                          interpret=False)

    def gradient(q_t, k_i, w_t, start, to_scores):
        return jax.vjp(lambda *xs: op(*xs, start), q_t, k_i, w_t)[1](
            to_scores)

    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for shape, dtype in (((1, 512, 16, 64), jnp.bfloat16),
                                  ((1, 8192, 64), jnp.bfloat16),
                                  ((1, 512, 16), jnp.float32),
                                  ((), jnp.int32),
                                  ((1, 512, 8192), jnp.float32))]
    return (gradient, avals) if grad else (op, avals[:4])


@pytest.mark.parametrize("t,calls", [(1000, 3), (1024, 6)],
                         ids=["plain_indexer", "index_kernels"])
def test_the_selected_attention_layer_cross_lowers_for_tpu(monkeypatch, t,
                                                           calls):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    grad, params, h = _selected_layer(t)    # 1000 pads to whole blocks
    exported = jax.export.export(jax.jit(grad), platforms=["tpu"])(params, h)
    text = exported.mlir_module()
    # the flash kernel's forward, kept across nn.remat, and its two
    # backward calls; over 1000 positions (tiles of 500) the indexer is
    # XLA's loops over tiles of queries, over 1024 the loops' bodies hold
    # the index scores' kernel where a tile selects and where the
    # alignment loss reads them, and the pullback
    assert text.count("tpu_custom_call") == calls
    assert "stablehlo.while" in text


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_the_selected_kernels_cross_lower_at_the_cell_s_shape(grad):
    fn, avals = _selected_kernels(grad)
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *avals).mlir_module()
    assert text.count("tpu_custom_call") == 1 + 2 * grad


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_the_index_kernels_cross_lower_at_the_cell_s_shape(grad):
    fn, avals = _index_kernels(grad)
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *avals).mlir_module()
    # one call either way: the pullback rebuilds the products itself and
    # keeps nothing of the forward call, which goes unused here
    assert text.count("tpu_custom_call") == 1


# tokens, hidden width and pair-buffer rows of each sequence cell's
# expert layers
ROWS_SHAPES = {"nemotron": (8192, 2688, 8192), "glm": (8192, 2048, 8192),
               "xing": (8192, 3584, 8192), "kimi": (8192, 2304, 8192),
               "keye": (8192, 2048, 16384), "mellum": (8192, 2304, 16384)}


def _row_kernels(name, sharding=None):
    """A pass's three movements of ``ops/expert_rows`` at a cell's
    shape, compiled (not interpreted), and their abstract operands."""
    from persia_tpu.ops import expert_rows

    tokens, hidden, rows = ROWS_SHAPES[name]

    def passes(source, token_of, count, acc, out, weight):
        return (expert_rows.take_rows(source, token_of, count,
                                      interpret=False),
                expert_rows.take_rows(acc, token_of, count,
                                      interpret=False),
                expert_rows.add_rows(acc, out, weight, token_of, count,
                                     interpret=False))

    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for shape, dtype in (((tokens, hidden), jnp.bfloat16),
                                  ((rows,), jnp.int32), ((1,), jnp.int32),
                                  ((tokens, hidden), jnp.float32),
                                  ((rows, hidden), jnp.bfloat16),
                                  ((rows,), jnp.float32))]
    return passes, avals


@pytest.mark.parametrize("name", sorted(ROWS_SHAPES))
def test_the_row_kernels_cross_lower_at_the_cells_shapes(name):
    fn, avals = _row_kernels(name)
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *avals).mlir_module()
    assert text.count("tpu_custom_call") == 3


def _aot_compile_rows() -> int:
    """Subprocess body: the pair-row kernels at every sequence cell's
    shape, for a v5e, no chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    failed = 0
    for name in sorted(ROWS_SHAPES):
        try:
            fn, avals = _row_kernels(name, sharding)
            compiled = jax.jit(fn).lower(*avals).compile()
            assert compiled.as_text().count("tpu_custom_call") == 3
            print(f"COMPILED rows {name}")
        except Exception as e:  # noqa: BLE001 — each reported
            failed += 1
            print(f"REFUSED rows {name}: {str(e)[:600]}")
    return failed


def _grouped_calls(name, sharding=None):
    """An expert layer's six megablox calls at a cell's shapes (both
    products, and both pullbacks: the product with the matrix transposed
    and the grouped outer product), and their abstract operands. The
    caller makes ``ops.interpret()`` answer compile."""
    from persia_tpu.ops.grouped_matmul import (
        grouped_matmul,
        grouped_matmul_pullback,
    )

    rows, hidden, width, wider, held = EXPERT_SHAPES[name]
    first = wider * width

    def layer(x, mid, w1, w2, sizes, g_pre, g_out):
        return (grouped_matmul(x, w1, sizes), grouped_matmul(mid, w2, sizes),
                *grouped_matmul_pullback(mid, w2, sizes, g_out),
                *grouped_matmul_pullback(x, w1, sizes, g_pre))

    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for shape, dtype in (((rows, hidden), jnp.bfloat16),
                                  ((rows, width), jnp.bfloat16),
                                  ((held, hidden, first), jnp.bfloat16),
                                  ((held, width, hidden), jnp.bfloat16),
                                  ((held + 1,), jnp.int32),
                                  ((rows, first), jnp.bfloat16),
                                  ((rows, hidden), jnp.bfloat16))]
    return layer, avals


@pytest.mark.parametrize("name", sorted(EXPERT_SHAPES))
def test_the_grouped_calls_cross_lower_at_the_cells_shapes(monkeypatch,
                                                           name):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, avals = _grouped_calls(name)
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *avals).mlir_module()
    assert text.count("tpu_custom_call") == 6


def _aot_compile_grouped() -> int:
    """Subprocess body: the grouped products at every sequence cell's
    shapes, for a v5e, no chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"     # the op: compile
    failed = 0
    for name in sorted(EXPERT_SHAPES):
        try:
            fn, avals = _grouped_calls(name, sharding)
            compiled = jax.jit(fn).lower(*avals).compile()
            assert compiled.as_text().count("tpu_custom_call") == 6
            print(f"COMPILED grouped {name}")
        except Exception as e:  # noqa: BLE001 — each reported
            failed += 1
            print(f"REFUSED grouped {name}: {str(e)[:600]}")
    return failed


def _aot_compile_selected() -> int:
    """Subprocess body: the ``S`` layer's gradient, and the flash
    kernels under a selection and the index scores' kernels alone at the
    cell's shape, for a v5e, no chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"     # the mixer's op: compile
    grad, params, h = _selected_layer(4096)
    placed = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        (params, h))
    failed = 0
    for name, fn, avals in (
            ("selected layer", grad, placed),
            ("selected forward kernel", *_selected_kernels(False, sharding)),
            ("selected gradient kernels", *_selected_kernels(True,
                                                             sharding)),
            ("selected index forward kernel", *_index_kernels(False,
                                                              sharding)),
            ("selected index gradient kernels", *_index_kernels(True,
                                                                sharding))):
        try:
            compiled = jax.jit(fn).lower(*avals).compile()
            assert "tpu_custom_call" in compiled.as_text()
            mem = compiled.memory_analysis()
            print(f"COMPILED {name}: {mem.temp_size_in_bytes} temporary "
                  f"bytes")
        except Exception as e:  # noqa: BLE001 — each reported
            failed += 1
            print(f"REFUSED {name}: {str(e)[:600]}")
    return failed


def _aot_compile_delta() -> int:
    """Subprocess body: the ``K`` layer's gradient, and the recurrence's
    kernels alone at the cell's shape, for a v5e, no chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"     # the mixer's op: compile
    grad, params, h = _delta_layer(2048)
    placed = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        (params, h))
    failed = 0
    for name, fn, avals in (
            ("kda layer", grad, placed),
            ("kda forward kernel", *_delta_kernels(False, sharding)),
            ("kda gradient kernels", *_delta_kernels(True, sharding))):
        try:
            compiled = jax.jit(fn).lower(*avals).compile()
            assert "tpu_custom_call" in compiled.as_text()
            mem = compiled.memory_analysis()
            print(f"COMPILED {name}: {mem.temp_size_in_bytes} temporary "
                  f"bytes")
        except Exception as e:  # noqa: BLE001 — each reported
            failed += 1
            print(f"REFUSED {name}: {str(e)[:600]}")
    return failed


def _aot_compile_conv() -> int:
    """Subprocess body: the short convolution's kernels at both cells'
    shapes, forward and pullback, for a v5e, no chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"     # the op: compile
    failed = 0
    for name, grad in itertools.product(sorted(CONV_SHAPES), (False, True)):
        what = f"conv {name} {'gradient' if grad else 'forward'}"
        try:
            compiled = jax.jit(_conv_kernels(name, grad, sharding)[0]).lower(
                *_conv_kernels(name, grad, sharding)[1]).compile()
            assert "tpu_custom_call" in compiled.as_text()
            print(f"COMPILED {what}")
        except Exception as e:  # noqa: BLE001 — each reported
            failed += 1
            print(f"REFUSED {what}: {str(e)[:600]}")
    return failed


def _aot_compile_all() -> int:
    """Subprocess body: compile every variant for a v5e with no chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # with libtpu installed this must work; a failure here fails the test
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    failed = 0
    for (t, dk, dv, dtype), (masked, grad), causal in itertools.product(
            AOT_SHAPES, VARIANTS, (False, True)):
        name = (f"T={t} dk={dk} dv={dv} {'masked' if masked else 'plain'} "
                f"{'grad' if grad else 'fwd'} causal={causal}")
        try:
            compiled = jax.jit(_attn_fn(masked, grad, causal)).lower(
                *_avals(t, dk, dv, dtype, sharding)).compile()
            assert "tpu_custom_call" in compiled.as_text()
            print(f"COMPILED {name}")
        except Exception as e:  # noqa: BLE001 — every variant reported
            failed += 1
            print(f"REFUSED {name}: {str(e)[:600]}")
    return failed


def _aot_subprocess(*what):
    # the one legitimate skip: no TPU compiler in this installation.
    # With libtpu present, a topology it cannot describe is a failure —
    # a skip would put back the false green this file exists to end
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler to run")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # libtpu wants these named when there is no TPU metadata to read
    env.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    env.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    r = subprocess.run([sys.executable, os.path.abspath(__file__), *what],
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0 and "REFUSED" not in r.stdout, (
        r.stdout[-4000:] + r.stderr[-2000:])
    return r.stdout


def test_attention_aot_compiles_for_v5e():
    out = _aot_subprocess()
    assert out.count("COMPILED") == 8 * len(AOT_SHAPES), out[-4000:]


def test_the_delta_rule_layer_aot_compiles_for_v5e():
    out = _aot_subprocess("delta")
    for what in ("layer", "forward kernel", "gradient kernels"):
        assert f"COMPILED kda {what}" in out, out[-4000:]


def test_the_selected_attention_layer_aot_compiles_for_v5e():
    out = _aot_subprocess("selected")
    for what in ("layer", "forward kernel", "gradient kernels",
                 "index forward kernel", "index gradient kernels"):
        assert f"COMPILED selected {what}" in out, out[-4000:]


def test_the_row_kernels_aot_compile_for_v5e():
    out = _aot_subprocess("rows")
    for name in sorted(ROWS_SHAPES):
        assert f"COMPILED rows {name}" in out, out[-4000:]


def test_the_grouped_calls_aot_compile_for_v5e():
    out = _aot_subprocess("grouped")
    for name in sorted(EXPERT_SHAPES):
        assert f"COMPILED grouped {name}" in out, out[-4000:]


def test_the_conv_kernels_aot_compile_for_v5e():
    out = _aot_subprocess("conv")
    for name, what in itertools.product(sorted(CONV_SHAPES),
                                        ("forward", "gradient")):
        assert f"COMPILED conv {name} {what}" in out, out[-4000:]


if __name__ == "__main__":
    sys.exit({("delta",): _aot_compile_delta,
              ("conv",): _aot_compile_conv,
              ("rows",): _aot_compile_rows,
              ("grouped",): _aot_compile_grouped,
              ("selected",): _aot_compile_selected}.get(
                  tuple(sys.argv[1:]), _aot_compile_all)())
