"""``ops.kda_scan`` (Kimi Delta Attention in chunks: in-chunk scores whose
decay is a vector over the key's channels, a unit-lower-triangular solve
a chunk, a chunk-to-chunk carry) against the recurrence it stands for,
stepped position by position in float32 (``delta_rule`` of the
benchmark's plain reference, ``benchmarks/chip/reference_kda_seq.py``).

Tolerances. Float32 products against the stepped recurrence: 2e-5 of
the largest entry, forward and gradients; the two differ by the order
of some thousand float32 roundings a chunk (the solve, the sums over
128 channels). bfloat16 products against float32 ones: 3e-2 of the
largest entry (8 bits of mantissa through five products in a row; the
gates, the running sums, every ``exp`` and the solve stay float32).
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

# the benchmark's plain reference steps the recurrence position by
# position, and imports nothing of the program
from reference_kda_seq import delta_rule  # noqa: E402

from persia_tpu.ops.kda_scan import (  # noqa: E402
    BLOCK,
    _unit_lower_inverse,
    kda_gate,
    kda_scan,
)

F32 = jnp.float32
INPUTS = ("q", "k", "v", "a", "a_log", "dt_bias", "beta")


def make(seed, bs=2, t=80, heads=3, dk=32, dv=24, a_scale=1.0, a_shift=0.0):
    """The seven inputs as the mixer hands them over: ``q`` and ``k``
    normed a head, the query scaled; ``a`` as projected; ``beta`` after
    its sigmoid; ``A_log`` and ``dt_bias`` from the published ranges."""
    keys = jax.random.split(jax.random.key(seed), 7)

    def normed(key):
        x = jax.random.normal(key, (bs, t, heads, dk), F32)
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    dt = jnp.exp(jax.random.uniform(keys[5], (heads * dk,), F32,
                                    np.log(1e-3), np.log(1e-1)))
    return {"q": normed(keys[0]) * dk ** -0.5, "k": normed(keys[1]),
            "v": jax.random.normal(keys[2], (bs, t, heads, dv), F32),
            "a": a_shift + a_scale * jax.random.normal(
                keys[3], (bs, t, heads, dk), F32),
            "a_log": jnp.log(jax.random.uniform(keys[4], (heads,), F32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "beta": jax.nn.sigmoid(jax.random.normal(
                keys[6], (bs, t, heads), F32))}


def chunked(x, chunk, dtype=F32):
    g = kda_gate(x["a"], x["a_log"], x["dt_bias"])
    with jax.default_matmul_precision("highest"):
        return kda_scan(x["q"], x["k"], x["v"], g, x["beta"], chunk=chunk,
                        compute_dtype=dtype)


def by_steps(x):
    with jax.default_matmul_precision("highest"):
        return delta_rule(x["q"], x["k"], x["v"],
                          kda_gate(x["a"], x["a_log"], x["dt_bias"]),
                          x["beta"])


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.isfinite(a).all()
    scale = np.abs(b).max() + 1e-30
    np.testing.assert_allclose(a / scale, b / scale, atol=tol)


@pytest.mark.parametrize("t", [128, 80], ids=["whole_chunks", "padded"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_form_is_the_recurrence(chunk, t):
    x = make(3, t=t)
    _close(chunked(x, chunk), by_steps(x), 2e-5)


@functools.lru_cache(maxsize=None)
def _both_gradients(chunk, t):
    """{input: gradient} of one probed sum, through the chunked form and
    through the stepped one; gates a tenth of a step and more, so that
    the decay's inputs have a gradient to speak of."""
    x = make(5, t=t, a_shift=3.0)
    probe = jax.random.normal(jax.random.key(9),
                              (2, t, 3, x["v"].shape[-1]), F32)
    return tuple(jax.jit(jax.grad(lambda y: jnp.sum(f(y) * probe)))(x)
                 for f in (lambda y: chunked(y, chunk), by_steps))


@pytest.mark.parametrize("name", INPUTS)
@pytest.mark.parametrize("chunk,t", [(16, 40), (64, 128), (64, 80)])
def test_every_input_s_gradient_is_the_recurrence_s(chunk, t, name):
    mine, theirs = _both_gradients(chunk, t)
    assert float(jnp.abs(theirs[name]).max()) > 0
    _close(mine[name], theirs[name], 2e-5)


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_decay_of_minus_twenty_a_step_overflows_nothing(chunk):
    """``A`` 16 and a softplus of order one: ``exp(-G_j)`` alone would
    pass float32's range within five positions."""
    x = make(7, t=96, a_shift=1.0)
    x["a_log"] = jnp.full_like(x["a_log"], np.log(16.0))
    x["dt_bias"] = jnp.zeros_like(x["dt_bias"])
    g = kda_gate(x["a"], x["a_log"], x["dt_bias"])
    assert float(g.min()) < -20 and float(g.mean()) < -10

    def loss(y):
        return jnp.sum(jnp.square(chunked(y, chunk)))

    value, grads = jax.value_and_grad(loss)(x)
    assert np.isfinite(float(value))
    for name, grad in grads.items():
        assert np.isfinite(np.asarray(grad)).all(), name
    _close(chunked(x, chunk), by_steps(x), 2e-5)


def test_bfloat16_products_stay_near_float32_ones():
    x = make(11, t=128)
    probe = jax.random.normal(jax.random.key(2), (2, 128, 3, 24), F32)
    _close(chunked(x, 64, jnp.bfloat16), chunked(x, 64), 3e-2)
    low, full = (jax.jit(jax.grad(lambda y: jnp.sum(
        chunked(y, 64, dtype) * probe)))(x) for dtype in (jnp.bfloat16, F32))
    for name in INPUTS:
        _close(low[name], full[name], 3e-2)


def test_a_repeated_key_is_corrected_not_added():
    """The same key twice with ``beta`` 1 and no decay: the second write
    replaces the first, and the state reads back the second value."""
    k = jnp.zeros((1, 32, 1, 16), F32).at[..., 0].set(1.0)
    v = jnp.arange(32, dtype=F32).reshape(1, 32, 1, 1) * jnp.ones((1, 1, 1, 8))
    o = kda_scan(k, k, v, jnp.zeros_like(k), jnp.ones((1, 32, 1), F32),
                 chunk=16, compute_dtype=F32)
    _close(o, v, 1e-6)


@pytest.mark.parametrize("n", [BLOCK, 4 * BLOCK, 8 * BLOCK])
def test_the_inverse_holds_where_keys_repeat(n):
    """All of ``A`` below the diagonal at one, as a history of one item
    makes it: the inverse is the first difference, exactly."""
    a = jnp.tril(jnp.ones((2, n, n), F32), -1)
    inv = _unit_lower_inverse(a)
    want = np.eye(n) - np.eye(n, k=-1)
    np.testing.assert_allclose(np.asarray(inv[0]), want, atol=1e-6)


@pytest.mark.parametrize("head_group", [1, 2])
def test_heads_worked_a_group_at_a_time_give_the_same(head_group):
    x = make(13, t=80, heads=4)
    g = kda_gate(x["a"], x["a_log"], x["dt_bias"])

    def loss(y, group):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.sin(kda_scan(
                y["q"], y["k"], y["v"], g, y["beta"], chunk=32,
                compute_dtype=F32, head_group=group)))

    at_once = jax.jit(jax.value_and_grad(lambda y: loss(y, 4)))(x)
    grouped = jax.jit(jax.value_and_grad(lambda y: loss(y, head_group)))(x)
    assert float(grouped[0]) == pytest.approx(float(at_once[0]), rel=1e-6)
    for name in ("q", "k", "v", "beta"):
        _close(grouped[1][name], at_once[1][name], 1e-6)


def test_a_chunk_that_blocks_do_not_tile_is_refused():
    x = make(1, t=48)
    with pytest.raises(ValueError, match="chunk 48"):
        chunked(x, 48)
