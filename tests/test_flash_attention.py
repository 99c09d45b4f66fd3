"""Pallas flash attention: interpret-mode parity with the XLA blockwise
implementation, gradient parity through the recompute backward, and the
compiled-on-TPU gate (PERSIA_TEST_TPU=1).

Block sizes are clamped to multiples of 128 (what lowers for TPU), so
the multi-block cases here use T of a few hundred with block 128; that
every variant also lowers and compiles for the chip is
tests/test_tpu_lowering.py's job."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu import metrics
from persia_tpu.ops.flash_attention import (
    EDGE,
    FIRST,
    LAST,
    _blocks,
    _clamp_block,
    block_schedule,
    flash_attention,
    flash_attention_fwd_pallas,
    flash_attention_masked,
)
from persia_tpu.parallel.ring_attention import (
    local_flash_attention,
    reference_attention,
)


def _qkv(b=2, h=2, t=96, dh=16, t_k=None, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    t_k = t if t_k is None else t_k
    q = jnp.asarray(rng.normal(size=(b, h, t, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(b, h, t_k, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(b, h, t_k, dh)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,block", [(256, 128), (384, 128), (300, 128),
                                     (100, 128)])  # last: T under a block
def test_fwd_matches_reference(causal, t, block):
    q, k, v = _qkv(t=t)
    ref = reference_attention(q, k, v, causal=causal)
    out = flash_attention_fwd_pallas(q, k, v, causal=causal,
                                     block_q=block, block_k=block,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fwd_cross_attention_lengths():
    q, k, v = _qkv(t=200, t_k=420)
    ref = reference_attention(q, k, v, causal=False)
    out = flash_attention_fwd_pallas(q, k, v, block_q=128, block_k=256,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fwd_bf16_matches_scan_impl():
    q, k, v = _qkv(t=256, dh=64, dtype=jnp.bfloat16)
    scan = local_flash_attention(q, k, v, causal=True, chunk_size=64)
    out = flash_attention_fwd_pallas(q, k, v, causal=True, block_q=128,
                                     block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(scan, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_grad_matches_xla_blockwise():
    q, k, v = _qkv(t=288)

    def loss_pallas(q, k, v):
        return jnp.mean(flash_attention(q, k, v, True, 128, 128, True) ** 2)

    def loss_xla(q, k, v):
        return jnp.mean(
            local_flash_attention(q, k, v, causal=True, chunk_size=32) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grad_cross_attention_lengths(causal):
    """Pallas bwd with t_q != t_k and padding on both grids."""
    q, k, v = _qkv(t=144, t_k=336)

    def loss_p(q, k, v):
        return jnp.mean(
            flash_attention(q, k, v, causal, 128, 128, True) ** 2)

    def loss_r(q, k, v):
        return jnp.mean(
            reference_attention(q, k, v, causal=causal) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_grad_bf16_finite_and_close():
    q, k, v = _qkv(t=256, dh=64, dtype=jnp.bfloat16)

    def loss_p(q, k, v):
        return jnp.mean(
            flash_attention(q, k, v, True, 128, 128, True).astype(
                jnp.float32) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.mean(reference_attention(
            q, k, v, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):  # dq, dk, AND dv — all within bf16 noise
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all())
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-1, atol=1e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_kv_mask_fwd_and_grad(causal):
    """Masked path: parity with reference_attention's kv_mask handling,
    including a fully-masked batch row (output and grads -> 0)."""
    q, k, v = _qkv(t=288)
    rng = np.random.default_rng(3)
    kv_mask = jnp.asarray(rng.random((2, 288)) > 0.3)
    kv_mask = kv_mask.at[1, :].set(False)  # row 1: nothing valid

    def loss_p(q, k, v):
        return jnp.mean(flash_attention_masked(
            q, k, v, kv_mask=kv_mask, causal=causal, block_q=128,
            block_k=128, interpret=True) ** 2)

    def loss_r(q, k, v):
        return jnp.mean(reference_attention(
            q, k, v, causal=causal, kv_mask=kv_mask) ** 2)

    out_p = flash_attention_masked(q, k, v, kv_mask=kv_mask, causal=causal,
                                   block_q=128, block_k=128,
                                   interpret=True)
    out_r = reference_attention(q, k, v, causal=causal, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(out_p[1]).max()) == 0.0
    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# (t_q, t_k, block_q, block_k): square and rectangular blocks, T ragged
# against the block on either side, t_q != t_k both ways (with t_q < t_k
# a causal call leaves whole key blocks without a query)
SCHEDULE_SHAPES = [
    (512, 512, 128, 128), (300, 300, 128, 128), (384, 640, 128, 256),
    (640, 384, 256, 128), (144, 336, 128, 128), (1000, 1000, 512, 256),
    (100, 100, 128, 128), (700, 260, 128, 256), (1024, 1024, 256, 512),
]


def _brute_force(t_q, t_k, block_q, block_k, causal):
    """Per block pair, from the full (padded) score mask: is some score
    live, is some score masked."""
    n_q, n_k = -(-t_q // block_q), -(-t_k // block_k)
    q_pos = np.arange(n_q * block_q)[:, None]
    k_pos = np.arange(n_k * block_k)[None, :]
    mask = (q_pos < t_q) & (k_pos < t_k)
    if causal:
        mask &= q_pos >= k_pos
    blocks = mask.reshape(n_q, block_q, n_k, block_k)
    return blocks.any(axis=(1, 3)), ~blocks.all(axis=(1, 3))


@pytest.mark.parametrize("by_key", [False, True])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t_q,t_k,block_q,block_k", SCHEDULE_SHAPES)
def test_schedule_matches_brute_force_mask(t_q, t_k, block_q, block_k,
                                           causal, by_key):
    """The table holds a pair exactly when some score of it is live,
    marks it EDGE exactly when some score of it is masked, and walks
    each accumulation row in ascending order, every row once, its first
    and last pair marked. A row with nothing live keeps one pair, fully
    masked, so that its output block is written."""
    live, masked = _brute_force(t_q, t_k, block_q, block_k, causal)
    qi, ki, flags = block_schedule(t_q, t_k, block_q, block_k, causal,
                                   by_key)
    assert qi.dtype == ki.dtype == flags.dtype == np.int32
    listed = np.zeros_like(live)
    listed[qi, ki] = True
    assert len(set(zip(qi.tolist(), ki.tolist()))) == qi.size
    row, col = (ki, qi) if by_key else (qi, ki)
    live_rows = live.any(axis=0 if by_key else 1)
    placeholders = ~live[qi, ki]
    np.testing.assert_array_equal(listed & live, live)
    # a placeholder only in a dead row, one a dead row, and masked
    assert not live_rows[row[placeholders]].any()
    assert placeholders.sum() == (~live_rows).sum()
    np.testing.assert_array_equal((flags & EDGE) != 0, masked[qi, ki])
    # rows in order, each once and whole; inside a row ascending
    new_row = np.r_[True, np.diff(row) != 0]
    np.testing.assert_array_equal(row[new_row], np.arange(live_rows.size))
    assert (np.diff(col)[~new_row[1:]] > 0).all()
    np.testing.assert_array_equal((flags & FIRST) != 0, new_row)
    np.testing.assert_array_equal((flags & LAST) != 0,
                                  np.r_[new_row[1:], True])


@pytest.mark.parametrize("block,pairs", [
    (512, [136, 256, 16]),      # the size until PR 34, and ISSUE 34's count
    (None, [36, 64, 8]),        # the default at T 8192: 1024 x 1024
])
def test_schedule_at_the_cells_shape_and_its_gauges(block, pairs):
    """T 8192, causal: in 512 x 512 blocks 136 pairs of the rectangle's
    256, 16 of them on the diagonal; the gauges say so once a call is
    built. A non-causal call walks the whole rectangle, no pair EDGE."""
    size = block or 1024
    for by_key in (False, True):
        qi, ki, flags = block_schedule(8192, 8192, size, size, True, by_key)
        on_edge = (flags & EDGE) != 0
        assert [qi.size, on_edge.sum()] == [pairs[0], pairs[2]]
        assert (qi[on_edge] == ki[on_edge]).all()
    qi, ki, flags = block_schedule(8192, 8192, size, size, False)
    assert qi.size == pairs[1] and not (flags & EDGE).any()
    x = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: flash_attention_masked(
        q, k, v, causal=True, block_q=block, block_k=block,
        interpret=True), x, x, x)
    reg = metrics.default_registry()
    assert [reg.gauge(f"flash_attention_pairs_{n}").value
            for n in ("walked", "dense", "edge")] == pairs


@pytest.mark.parametrize("t,dh,dtype,want", [
    (8192, 128, jnp.bfloat16, 1024), (8192, 256, jnp.bfloat16, 1024),
    (4096, 256, jnp.bfloat16, 1024), (2048, 256, jnp.bfloat16, 1024),
    (2048, 128, jnp.bfloat16, 1024), (8192, 64, jnp.float32, 1024),
    (8192, 256, jnp.float32, 512),   # a 1 MiB tile: does not fit VMEM
    (8192, 512, jnp.bfloat16, 512),
    (300, 128, jnp.bfloat16, 384),   # clamped to T, in 128s
    (100, 8, jnp.float32, 128),
])
def test_default_blocks_follow_the_tile_bytes(t, dh, dtype, want):
    """No caller sets a block size; the file picks it from what the call
    can see, and a request still wins (clamped as ever)."""
    assert _blocks(None, None, t, t, dh, dtype) == (want, want)
    assert _blocks(200, 64, t, t, dh, dtype) == (
        _clamp_block(200, t), _clamp_block(64, t))


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("t_q,t_k,block_q,block_k", [
    (300, 300, 256, 128), (300, 300, 128, 256), (144, 336, 128, 256),
    (420, 200, 128, 128)])
def test_causal_rectangular_blocks_ragged_t(t_q, t_k, block_q, block_k,
                                            masked):
    """Causal forward and gradients with block_q != block_k, T ragged
    against both and t_q != t_k, against reference_attention; with a
    kv_mask one batch row is fully masked (output and gradients 0)."""
    q, k, v = _qkv(t=t_q, t_k=t_k, seed=7)
    kv_mask = None
    if masked:
        rng = np.random.default_rng(11)
        kv_mask = jnp.asarray(rng.random((2, t_k)) > 0.3).at[1, :].set(False)

    def fwd_p(q, k, v):
        return flash_attention_masked(
            q, k, v, kv_mask=kv_mask, causal=True, block_q=block_q,
            block_k=block_k, interpret=True)

    def fwd_r(q, k, v):
        return reference_attention(q, k, v, causal=True, kv_mask=kv_mask)

    out = fwd_p(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fwd_r(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    if masked:
        assert float(jnp.abs(out[1]).max()) == 0.0
    gp = jax.grad(lambda *x: jnp.mean(fwd_p(*x) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *x: jnp.mean(fwd_r(*x) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# --- key width and value width apart, the softmax scale an argument --------


def _plain_attention(q, k, v, causal, scale, kv_mask=None):
    """softmax(scale q k^T) v as a full score matrix, float32."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") * scale
    keep = jnp.ones(s.shape[-2:], bool)
    if causal:
        keep = jnp.tril(keep)
    keep = keep[None, None]
    if kv_mask is not None:
        keep = keep & kv_mask[:, None, None, :]
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("dk,dv,t,scale", [
    (192, 128, 300, None),      # the hyper-connected tower's heads
    (24, 16, 260, 0.31),        # a small pair, the scale the caller's
    (16, 24, 200, None),        # values wider than keys
])
def test_unequal_widths_match_plain_attention(dk, dv, t, scale, masked):
    """q and k ``dk`` wide, v, the output and its cotangent ``dv``: the
    forward, its ``lse`` and all three gradients (dq and dk at ``dk``,
    dv at ``dv``) against the full score matrix, interpreted, causal,
    T over two blocks with a ragged tail. float32 in and out: 2e-5
    absolute on values of order one is the accumulation order's
    round-off, as in the equal-width tests above."""
    rng = np.random.default_rng(dk + dv)
    q, k = (jnp.asarray(rng.normal(size=(1, 2, t, dk)), jnp.float32)
            for _ in range(2))
    v, w = (jnp.asarray(rng.normal(size=(1, 2, t, dv)), jnp.float32)
            for _ in range(2))
    mask = None
    if masked:
        mask = jnp.asarray(rng.random((1, t)) > 0.2).at[:, 0].set(True)
    factor = 1.0 / np.sqrt(dk) if scale is None else scale

    def mine(q, k, v):
        return flash_attention_masked(q, k, v, kv_mask=mask, causal=True,
                                      block_q=128, block_k=128,
                                      interpret=True, scale=scale)

    def plain(q, k, v):
        return _plain_attention(q, k, v, True, factor, mask)

    out = mine(q, k, v)
    assert out.shape == (1, 2, t, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *x: jnp.sum(w * mine(*x)), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *x: jnp.sum(w * plain(*x)), argnums=(0, 1, 2))(
        q, k, v)
    assert [g.shape[-1] for g in got] == [dk, dk, dv]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_the_scale_defaults_to_the_key_width_s():
    """No scale given is ``1 / sqrt(dk)`` whatever ``dv``, bit for bit
    the explicit call."""
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.normal(size=(1, 1, 130, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 1, 130, 8)), jnp.float32)
    kw = dict(causal=True, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(flash_attention_fwd_pallas(q, k, v, **kw)),
        np.asarray(flash_attention_fwd_pallas(q, k, v, scale=24 ** -0.5,
                                              **kw)))


def test_sequence_tower_pallas_impl():
    """SequenceSelfAttention(attn_impl='pallas') matches the xla impl
    through the flax module (single-device path)."""
    from flax import linen as nn  # noqa: F401 - ensures flax import ok

    from persia_tpu.models.seq import SequenceSelfAttention

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 40, 16)), jnp.float32)
    mask = jnp.asarray(rng.random((2, 40)) > 0.2)
    outs = {}
    for impl in ("xla", "pallas"):
        m = SequenceSelfAttention(num_heads=2, causal=True,
                                  compute_dtype=jnp.float32,
                                  attn_impl=impl)
        variables = m.init(jax.random.key(0), x, mask)
        outs[impl] = m.apply(variables, x, mask)
    np.testing.assert_allclose(np.asarray(outs["pallas"]),
                               np.asarray(outs["xla"]),
                               rtol=2e-4, atol=2e-4)


def test_compiled_on_tpu():
    """Compiled validation + timing vs the XLA scan implementation —
    real hardware only (interpret covers CPU)."""
    import os
    import time

    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs real TPU hardware")
    if not os.environ.get("PERSIA_TEST_TPU"):
        pytest.skip("set PERSIA_TEST_TPU=1 to run hardware validation")
    q, k, v = _qkv(b=4, h=8, t=4096, dh=128, dtype=jnp.bfloat16)
    f_pallas = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
    f_scan = jax.jit(lambda q, k, v: local_flash_attention(
        q, k, v, causal=True, chunk_size=512))
    ref = f_scan(q, k, v)
    out = f_pallas(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)
    for fn, name in ((f_scan, "xla-scan"), (f_pallas, "pallas")):
        fn(q, k, v).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(q, k, v)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / 10
        flops = 2 * 4 * 8 * 4096 * 4096 * 128
        print(f"{name}: {dt * 1e3:.2f} ms/call "
              f"({flops / dt / 1e12:.1f} TFLOP/s)")
    # train step (fwd+bwd) comparison: pallas bwd kernels vs scan vjp
    g_pallas = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    g_scan = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        local_flash_attention(q, k, v, causal=True,
                              chunk_size=512).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    gp = g_pallas(q, k, v)
    gs = g_scan(q, k, v)
    for a, b in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-2)
    for fn, name in ((g_scan, "grad xla-scan"), (g_pallas, "grad pallas")):
        jax.block_until_ready(fn(q, k, v))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 5
        print(f"{name}: {dt * 1e3:.2f} ms/call")


# --- a sliding window: the band's block pairs, its edge masks ----------------


def _brute_force_band(t, block_q, block_k, window):
    """Per block pair of a causal self-attention call under ``window``,
    from the full (padded) score mask: is some score live, is some score
    masked."""
    n_q, n_k = -(-t // block_q), -(-t // block_k)
    q_pos = np.arange(n_q * block_q)[:, None]
    k_pos = np.arange(n_k * block_k)[None, :]
    mask = ((q_pos < t) & (k_pos < t) & (q_pos >= k_pos)
            & (q_pos - k_pos < window))
    blocks = mask.reshape(n_q, block_q, n_k, block_k)
    return blocks.any(axis=(1, 3)), ~blocks.all(axis=(1, 3))


@pytest.mark.parametrize("by_key", [False, True])
@pytest.mark.parametrize("window", [1, 128, 200, 1024, "T"])
@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("t", [300, 1024, 2048])
def test_band_schedule_matches_brute_force_mask(t, block, window, by_key):
    """Under a window the table holds a pair exactly when some score of
    it is live and marks it EDGE exactly when some score of it is masked
    (the diagonal, the band's lower edge, a padded tail); rows are walked
    in order, each once, first and last marked. A window of T or more is
    the causal list itself."""
    window = t if window == "T" else window
    live, masked = _brute_force_band(t, block, block, window)
    qi, ki, flags = block_schedule(t, t, block, block, True, by_key,
                                   window=window)
    listed = np.zeros_like(live)
    listed[qi, ki] = True
    np.testing.assert_array_equal(listed, live)     # no placeholder needed
    assert len(set(zip(qi.tolist(), ki.tolist()))) == qi.size
    np.testing.assert_array_equal((flags & EDGE) != 0, masked[qi, ki])
    row, col = (ki, qi) if by_key else (qi, ki)
    new_row = np.r_[True, np.diff(row) != 0]
    np.testing.assert_array_equal(row[new_row], np.arange(live.shape[0]))
    assert (np.diff(col)[~new_row[1:]] > 0).all()
    np.testing.assert_array_equal((flags & FIRST) != 0, new_row)
    np.testing.assert_array_equal((flags & LAST) != 0,
                                  np.r_[new_row[1:], True])
    if window >= t:
        for got, want in zip((qi, ki, flags), block_schedule(
                t, t, block, block, True, by_key)):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape", SCHEDULE_SHAPES)
def test_no_window_is_the_causal_list(shape):
    for by_key in (False, True):
        for got, want in zip(
                block_schedule(*shape, True, by_key, window=None),
                block_schedule(*shape, True, by_key)):
            np.testing.assert_array_equal(got, want)


def test_a_call_without_a_window_lowers_as_before():
    """``window=None`` passed or left out: the same lowered text, the
    forward and the whole gradient, so the callers that pass no window
    run the program they ran before the argument was there."""
    x = jax.ShapeDtypeStruct((1, 2, 300, 32), jnp.bfloat16)

    def text(**kw):
        def loss(q, k, v):
            return flash_attention_masked(
                q, k, v, causal=True, block_q=128, block_k=128,
                interpret=True, **kw).astype(jnp.float32).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).as_text()

    assert text(window=None) == text()
    assert text(window=128) != text()


@pytest.mark.parametrize("dh,t,window,block", [
    (16, 300, 100, 128),    # ragged T, the band over two key blocks
    (32, 384, 128, 128),    # the band a block wide: no interior pair
    (32, 300, 1, 128),      # each query itself alone
    (64, 260, 200, 256),    # one query block, a band over most of it
])
def test_windowed_kernels_match_a_masked_softmax(dh, t, window, block):
    """Forward and all three gradients of the interpreted band kernels
    against a plain softmax under ``t - window < s <= t``, float32: 2e-5
    absolute on outputs of order one, 1e-4 on gradients, as the causal
    tests above."""
    q, k, v = _qkv(b=1, h=2, t=t, dh=dh, seed=dh + window)
    w = jnp.asarray(np.random.default_rng(window).normal(size=q.shape),
                    jnp.float32)
    pos = np.arange(t)
    band = jnp.asarray((pos[:, None] >= pos[None, :])
                       & (pos[:, None] - pos[None, :] < window))

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       precision="highest") / np.sqrt(dh)
        s = jnp.where(band[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                          precision="highest")

    def mine(q, k, v):
        return flash_attention_masked(q, k, v, causal=True, block_q=block,
                                      block_k=block, interpret=True,
                                      window=window)

    np.testing.assert_allclose(np.asarray(mine(q, k, v)),
                               np.asarray(plain(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *x: jnp.sum(w * mine(*x)), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *x: jnp.sum(w * plain(*x)), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_a_window_is_a_causal_band():
    q, k, v = _qkv(t=128)
    for bad in ({"causal": False, "window": 64},
                {"causal": True, "window": 0}):
        with pytest.raises(ValueError, match="window"):
            flash_attention_fwd_pallas(q, k, v, interpret=True, **bad)


@pytest.mark.parametrize("block,pairs", [(None, [15, 15]), (512, [45, 30])])
def test_the_window_gauges_read_the_band_from_the_schedule(block, pairs):
    """T 8192, window 1024: 15 pairs of 64 a head in the 1024 x 1024
    blocks ``_blocks`` picks, all of them edge pairs; 45 of 256 in 512s,
    30 on an edge. Building the call sets the window's own gauges and
    leaves the causal calls' as the causal call built last set them;
    nothing runs a kernel."""
    x = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16)
    reg = metrics.default_registry()

    def build(**kw):
        jax.eval_shape(jax.grad(lambda q, k, v: flash_attention_masked(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=True, **kw).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), x, x, x)

    build()
    causal = [reg.gauge(f"flash_attention_pairs_{n}").value
              for n in ("walked", "dense", "edge")]
    build(window=1024)
    assert [reg.gauge(f"flash_attention_window_pairs_{n}").value
            for n in ("walked", "edge")] == pairs
    assert [reg.gauge(f"flash_attention_pairs_{n}").value
            for n in ("walked", "dense", "edge")] == causal
