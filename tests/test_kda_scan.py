"""``ops.kda_scan`` (Kimi Delta Attention in chunks: in-chunk scores whose
decay is a vector over the key's channels, a unit-lower-triangular solve
a chunk, a chunk-to-chunk carry; two Pallas kernels behind a
``custom_vjp``, interpreted here) against the recurrence it stands for,
stepped position by position in float32 under autodiff (``delta_rule``
of the benchmark's plain reference,
``benchmarks/chip/reference_kda_seq.py``).

Tolerances. Float32 products against the stepped recurrence: 2e-5 of
the largest entry, forward and gradients; the two differ by the order
of some thousand float32 roundings a chunk (the solve, the sums over
128 channels). ``A_log``'s gradient is the sum over every position and
channel of a head of ``dg g``, terms of both signs that add to thirty
times the sum here, so it shows a rounding that the terms share: the
kernels' reads 1.3e-5 of it at most on these shapes (4e-6 to 1.3e-5
against the same recurrence stepped in float64), because a diagonal
block's pairs, whose parts of the running sum's gradient cancel inside
the block, are summed back inside the block only.
bfloat16 products against float32 ones: 3e-2 of the largest entry (8
bits of mantissa through five products in a row; the gates, the running
sums, every ``exp`` and the solve stay float32).
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

# the benchmark's plain reference steps the recurrence position by
# position, and imports nothing of the program
from reference_kda_seq import delta_rule  # noqa: E402
from test_attention_residuals import _pallas_kernels  # noqa: E402

from persia_tpu.models import hybrid_seq  # noqa: E402
from persia_tpu.ops.kda_scan import (  # noqa: E402
    BLOCK,
    RESIDUAL_NAMES,
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
def _both_gradients(chunk, t, **sizes):
    """{input: gradient} of one probed sum, through the chunked form and
    through the stepped one; gates a tenth of a step and more, so that
    the decay's inputs have a gradient to speak of."""
    x = make(5, t=t, a_shift=3.0, **sizes)
    probe = jax.random.normal(jax.random.key(9), x["v"].shape, F32)
    return tuple(jax.jit(jax.grad(lambda y: jnp.sum(f(y) * probe)))(x)
                 for f in (lambda y: chunked(y, chunk), by_steps))


def _same_gradient(mine, theirs, name):
    assert float(jnp.abs(theirs[name]).max()) > 0
    _close(mine[name], theirs[name], 2e-5)


@pytest.mark.parametrize("name", INPUTS)
@pytest.mark.parametrize("chunk,t", [(16, 40), (64, 128), (64, 80)])
def test_every_input_s_gradient_is_the_recurrence_s(chunk, t, name):
    _same_gradient(*_both_gradients(chunk, t), name)


@pytest.mark.parametrize("width", [16, 128])
def test_the_kernels_at_a_head_s_width(width):
    """The rehearsal tower's heads (a block narrower than a lane tile,
    which only the interpreter takes) and the published ones."""
    sizes = dict(bs=1, heads=2, dk=width, dv=width)
    x = make(3, t=48, **sizes)
    _close(chunked(x, 16), by_steps(x), 2e-5)
    mine, theirs = _both_gradients(16, 48, **sizes)
    for name in INPUTS:
        _same_gradient(mine, theirs, name)


def test_several_histories_in_a_batch_do_not_meet():
    """The state starts empty at every history and every head: a batch
    of three gives, value and gradient, what its histories give alone."""
    x = make(17, bs=3, t=144, heads=2)    # more chunks than a grid step's

    def loss(y):
        return jnp.sum(jnp.sin(chunked(y, 16)))

    def history(y, b):
        return {n: v[b:b + 1] if v.ndim > 1 and n != "dt_bias" else v
                for n, v in y.items()}

    both = jax.jit(jax.value_and_grad(loss))    # two shapes, two programs
    together = both(x)
    alone = [both(history(x, b)) for b in range(3)]
    assert float(together[0]) == pytest.approx(
        sum(float(v) for v, _ in alone), rel=1e-6)
    for name in ("q", "k", "v", "a", "beta"):
        _close(together[1][name],
               jnp.concatenate([g[name] for _, g in alone]), 1e-6)


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
    inv = _unit_lower_inverse(jnp.tril(jnp.ones((2, n, n), F32), -1))
    want = np.eye(n) - np.eye(n, k=-1)
    np.testing.assert_allclose(np.asarray(inv[1]), want, atol=1e-6)


@pytest.mark.parametrize("forwards", [1, 2], ids=["kept", "default_policy"])
def test_a_delta_rule_layer_s_gradient_runs_the_forward_once(forwards):
    """Under the tower's ``nn.remat``, whose policy keeps the op's output
    and its chunks' entering states (``RESIDUAL_NAMES``), the rebuilt
    layer does not run the forward kernel again; under the default
    policy it does. The backward kernel runs once either way."""
    keep = RESIDUAL_NAMES if forwards == 1 else ()
    layer = nn.remat(
        hybrid_seq._Layer,
        policy=jax.checkpoint_policies.save_only_these_names(*keep))(
            hybrid_seq.DeltaAttention(heads=2, head_dim=16, chunk=16,
                                      compute_dtype=F32, parent=None),
            "kda_attention", 1e-5, F32)
    h = jax.random.normal(jax.random.key(0), (1, 40, 32), F32)
    params = layer.init(jax.random.key(1), h)
    kernels = _pallas_kernels(jax.make_jaxpr(jax.value_and_grad(
        lambda p: jnp.sum(layer.apply(p, h) ** 2)))(params).jaxpr)
    assert kernels.count("_forward_kernel") == forwards
    assert kernels.count("_backward_kernel") == 1
    assert len(kernels) == forwards + 1


def test_a_chunk_that_blocks_do_not_tile_is_refused():
    x = make(1, t=48)
    with pytest.raises(ValueError, match="chunk 48"):
        chunked(x, 48)
