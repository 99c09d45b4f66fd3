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

from persia_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_fwd_pallas,
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
    from persia_tpu.ops.flash_attention import flash_attention_masked

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
