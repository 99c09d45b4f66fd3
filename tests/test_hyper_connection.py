"""The hyper-connection kernels (``persia_tpu/ops/hyper_connection.py``,
interpreted on the CPU) against the sublayer's plain ``jnp`` formula,
written out here as ``_HyperLayer`` had it before the kernels: the maps
from a float32 copy of the flattened streams at ``Precision.HIGHEST``,
the read-out and the mix as sums of broadcast products over a (.., n, C)
view, autodiff through all of it. Each kernel alone, then the two
``jax.custom_vjp``s' gradients against ``jax.grad`` of the formula. One
thing is not as the sublayer had it, here as in the kernels: ``u``
reaches the mixer in float32, where the sublayer cast it to the state's
dtype first (``ops/hyper_connection.py`` says why).

float32 state: 1e-5 of a result's largest entry. bfloat16 state: the
rounding of one cast, 2^-8 of the largest entry where one side is the
formula in float32 on the same bfloat16 inputs, 2^-7 where both sides
round (the formula adds three bfloat16 terms into the state's gradient,
the kernels round their float32 sum once).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from persia_tpu.models.hybrid_seq import sinkhorn
from persia_tpu.ops import hyper_connection as hc

F32, BF16 = jnp.float32, jnp.bfloat16
EPS, ITERS, CLAMP = 1e-6, 20, (-30.0, 30.0)

# streams x features a stream x positions (256: whole blocks of every
# kernel; 200: under one mix block and 56 rows short of two read-out
# blocks) x the state's dtype
CASES = [pytest.param(n, c, t, dt, id=f"{n}x{c}-{t}-{jnp.dtype(dt).name}")
         for n in (2, 4) for c in (128, 384) for t in (256, 200)
         for dt in (F32, BF16)]


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    scale = np.abs(want).max() + 1e-30
    np.testing.assert_allclose(got / scale, want / scale, atol=tol)


def _tol(dtype, both_round=False):
    if dtype == F32:
        return 1e-5
    return 2.0 ** -7 if both_round else 2.0 ** -8


def _inputs(n, c, t, dtype, seed=0):
    rng = np.random.default_rng(seed + 7 * n + c + t)

    def normal(*dims, scale=1.0, dtype=F32):
        return jnp.asarray(rng.normal(size=dims) * scale, dtype)

    widths = (n, n, n * n)
    return {
        "x": normal(2, t // 2, n * c, dtype=dtype),
        "phi": [normal(n * c, w, scale=0.05) for w in widths],
        "bias": [normal(w, scale=0.3) for w in widths],
        "scale": [jnp.asarray([v], F32) for v in (0.7, 0.5, 0.9)],
        "w": normal(c, c, scale=0.1),       # the stand-in mixer's
        "ct": normal(2, t // 2, n * c),     # weights of the loss
    }


def _mixer(u, w):
    return jnp.tanh(jnp.dot(u.astype(F32), w,
                            precision=lax.Precision.HIGHEST)).astype(u.dtype)


# --- the formula, as the sublayer had it ------------------------------------


def plain_maps(x4, phi, bias, scale):
    n = x4.shape[-2]
    z = x4.reshape(*x4.shape[:-2], -1).astype(F32)
    m = (jnp.dot(z, jnp.concatenate(phi, axis=-1),
                 precision=lax.Precision.HIGHEST)
         * lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True) + EPS))
    pre, post, res = (a * part + b for a, b, part in zip(
        scale, bias, jnp.split(m, [n, 2 * n], axis=-1)))
    pre, post = jax.nn.sigmoid(pre), 2 * jax.nn.sigmoid(post)
    res = sinkhorn(jnp.clip(res, *CLAMP).reshape(*m.shape[:-1], n, n),
                   ITERS, EPS)
    return m, pre, post, res


def plain_read_out(x4, pre):
    n = x4.shape[-2]
    return sum(pre[..., j, None] * x4[..., j, :] for j in range(n))


def plain_mix(x4, y, res, post):
    n = x4.shape[-2]
    mixed = sum(res[..., :, j, None] * x4[..., None, j, :] for j in range(n))
    return (mixed + post[..., None] * y[..., None, :]).astype(x4.dtype)


def plain_layer(x, phi, bias, scale, w, n, maps=plain_maps):
    x4 = x.reshape(*x.shape[:-1], n, -1)
    _, pre, post, res = maps(x4, phi, bias, scale)
    # the one place that is not as the sublayer had it: no cast of u to
    # the state's dtype before the mixer
    y = _mixer(plain_read_out(x4, pre), w).astype(x.dtype)
    return plain_mix(x4, y, res, post).reshape(x.shape)


def fused_layer(x, phi, bias, scale, w, n):
    u, m, carry = hc.read_out(x, jnp.concatenate(phi, axis=-1), scale[0],
                              bias[0], streams=n, eps=EPS)
    post, res = (a * part + b for a, b, part in zip(
        scale[1:], bias[1:], jnp.split(m, [n, 2 * n], axis=-1)[1:]))
    post = 2 * jax.nn.sigmoid(post)
    res = sinkhorn(jnp.clip(res, *CLAMP).reshape(*m.shape[:-1], n, n),
                   ITERS, EPS)
    return hc.mix(carry, _mixer(u, w).astype(x.dtype), res, post)


# --- each kernel alone -------------------------------------------------------


@pytest.mark.parametrize("n,c,t,dtype", CASES)
def test_the_forward_kernels_match_the_formula(n, c, t, dtype):
    """``read_out``: ``m`` and ``u`` to float32 rounding whatever the
    state's dtype (the product onto the maps' columns and the read-out's
    sum see the state exactly, and ``u`` leaves in float32), the mix's
    ``x'`` to one cast; ``carry`` is the state."""
    p = _inputs(n, c, t, dtype)
    x, x4 = p["x"], p["x"].reshape(2, t // 2, n, c)
    m, pre, post, res = plain_maps(x4, p["phi"], p["bias"], p["scale"])
    u, got_m, carry = hc.read_out(
        x, jnp.concatenate(p["phi"], axis=-1), p["scale"][0], p["bias"][0],
        streams=n, eps=EPS)
    assert carry is x or np.array_equal(np.asarray(carry, np.float32),
                                        np.asarray(x, np.float32))
    assert (u.dtype, got_m.dtype, u.shape) == (F32, F32, (2, t // 2, c))
    _close(got_m, m, 1e-5)
    _close(u, plain_read_out(x4.astype(F32), pre), 1e-5)
    y = _mixer(u, p["w"]).astype(dtype)
    got = hc.mix(x, y, res, post)
    assert (got.dtype, got.shape) == (dtype, x.shape)
    _close(got, plain_mix(x4.astype(F32), y.astype(F32), res,
                          post).reshape(x.shape), _tol(dtype))


@pytest.mark.parametrize("n,c,t,dtype", CASES)
def test_the_backward_kernels_match_the_formula(n, c, t, dtype):
    """The mix's backward against ``jax.vjp`` of the formula in float32
    on the same inputs; ``dpre`` and the read-out's last kernel against
    their sums written as einsums."""
    p = _inputs(n, c, t, dtype, seed=1)
    rng = np.random.default_rng(n + c + t)
    x = p["x"].reshape(t, n * c)
    x4 = x.reshape(t, n, c).astype(F32)
    y = jnp.asarray(rng.normal(size=(t, c)), dtype)
    du = jnp.asarray(rng.normal(size=(t, c)), F32)
    g, dcarry = (jnp.asarray(rng.normal(size=(t, n * c)), dtype)
                 for _ in range(2))
    res = jax.nn.softmax(jnp.asarray(rng.normal(size=(t, n, n)), F32))
    post, pre = (jax.nn.sigmoid(jnp.asarray(rng.normal(size=(t, n)), F32))
                 for _ in range(2))
    _, vjp = jax.vjp(lambda x4, y, res, post: plain_mix(x4, y, res, post),
                     x4, y.astype(F32), res, post)
    want = vjp(g.reshape(t, n, c).astype(F32))
    got = hc.mix_bwd(x, y, res.reshape(t, n * n), post, g, interpret=True)
    assert [a.dtype for a in got] == [dtype, dtype, F32, F32]
    _close(got[0], want[0].reshape(t, n * c), _tol(dtype))
    _close(got[1], want[1], _tol(dtype))
    _close(got[2], want[2].reshape(t, n * n), 1e-5)
    _close(got[3], want[3], 1e-5)

    _close(hc.read_out_dpre(x, du, streams=n, interpret=True),
           jnp.einsum("tc,tnc->tn", du.astype(F32), x4,
                      precision=lax.Precision.HIGHEST), 1e-5)

    phi = jnp.concatenate(p["phi"], axis=-1)
    w = phi.shape[-1]
    factor = jnp.asarray(rng.normal(size=(t, 1)) * 0.1, F32)
    rdm = jnp.asarray(rng.normal(size=(t, w)), F32)
    dx, dphi = hc.read_out_bwd(dcarry, x, du, pre, factor, rdm, phi,
                               streams=n, interpret=True)
    z = x.astype(F32)
    want_dx = (dcarry.astype(F32).reshape(t, n, c)
               + pre[:, :, None] * du.astype(F32)[:, None, :]
               + jnp.dot(rdm, phi.T,
                         precision=lax.Precision.HIGHEST).reshape(t, n, c)
               + factor[:, :, None] * x4)
    assert (dx.dtype, dphi.dtype, dphi.shape) == (dtype, F32, phi.shape)
    _close(dx, want_dx.reshape(t, n * c), _tol(dtype))
    _close(dphi, jnp.dot(z.T, rdm, precision=lax.Precision.HIGHEST), 1e-5)


# --- the custom_vjps against autodiff of the formula -------------------------


def _grads(layer, p, n):
    def loss(x, phi, bias, scale, w):
        return jnp.sum(p["ct"] * layer(x, phi, bias, scale, w, n).astype(F32))

    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        p["x"], p["phi"], p["bias"], p["scale"], p["w"])


@pytest.mark.parametrize("n,c,t,dtype", CASES)
def test_the_gradients_match_autodiff_of_the_formula(n, c, t, dtype):
    """With respect to the state, the mixer's weights (so through ``u``
    and ``y``) and each of the nine map leaves: ``phi``, bias and scale
    of ``pre``, ``post`` and ``res``."""
    p = _inputs(n, c, t, dtype, seed=2)
    _close(fused_layer(p["x"], p["phi"], p["bias"], p["scale"], p["w"], n),
           plain_layer(p["x"], p["phi"], p["bias"], p["scale"], p["w"], n),
           _tol(dtype, both_round=True))
    got, want = _grads(fused_layer, p, n), _grads(plain_layer, p, n)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    names = ["x"] + [f"{leaf}_{m}" for leaf in ("phi", "bias", "scale")
                     for m in ("pre", "post", "res")] + ["w"]
    for name, a, b in zip(names, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype, name
        # a scale's gradient is one number, the sum of every position's
        # terms of both signs: five times the room
        room = 5 if a.size == 1 else 1
        try:
            _close(a, b, room * _tol(dtype, both_round=True))
        except AssertionError as e:
            raise AssertionError(f"{name}: {e}") from None


def test_the_gradient_still_runs_through_every_sinkhorn_round():
    """The stream map's leaves get the gradient of all twenty rounds:
    the formula with the map's gradient stopped before its last round
    gives another ``phi_res`` gradient, and the kernels' path gives the
    full one."""
    n, c, t = 4, 128, 64
    p = _inputs(n, c, t, F32, seed=3)

    def last_round_only(x4, phi, bias, scale):
        m, pre, post, _ = plain_maps(x4, phi, bias, scale)
        logits = jnp.clip(scale[2] * m[..., 2 * n:] + bias[2],
                          *CLAMP).reshape(*m.shape[:-1], n, n)
        res = lax.stop_gradient(sinkhorn(logits, ITERS - 1, EPS)) * jnp.exp(
            logits - lax.stop_gradient(logits))
        res = res / (jnp.sum(res, axis=-1, keepdims=True) + EPS)
        return m, pre, post, res / (jnp.sum(res, axis=-2, keepdims=True)
                                    + EPS)

    got, want = _grads(fused_layer, p, n), _grads(plain_layer, p, n)
    short = _grads(lambda *a: plain_layer(*a, maps=last_round_only), p, n)
    for leaf in (1, 2, 3):      # phi, bias, scale of the stream map
        _close(got[leaf][2], want[leaf][2], 1e-5)
    full = np.asarray(want[1][2])
    assert np.abs(np.asarray(short[1][2]) - full).max() > 1e-2 * np.abs(
        full).max()


# --- shapes the entries take -------------------------------------------------


@pytest.mark.parametrize("rows,want", [(8192, (256, 8192)), (200, (208, 208)),
                                       (300, (256, 512)), (16, (16, 16))])
def test_rows_are_blocked_and_padded_to_whole_blocks(rows, want):
    assert hc._row_block(rows, 256) == want


@pytest.mark.parametrize("c,want", [(3584, 896), (384, 384), (128, 128),
                                    (1024, 512), (64, 64), (200, 200)])
def test_features_are_blocked_by_lane_tiles_that_divide_them(c, want):
    """The xing cell's 3584 = 28 tiles goes in blocks of 7; a width no
    lane tile divides is one block (the interpreter takes it, the chip's
    compiler would not)."""
    assert hc._feature_block(c, hc.FEATURES) == want


def test_a_float32_is_three_bfloat16_parts_exactly():
    a = jnp.asarray(np.random.default_rng(5).normal(size=(64, 48))
                    * np.logspace(-6, 6, 48), F32)
    parts = hc._bf16_parts(a)
    assert [q.dtype for q in parts] == [BF16] * 3
    np.testing.assert_array_equal(
        np.asarray(sum(q.astype(F32) for q in parts)), np.asarray(a))
    assert hc._bf16_parts(a.astype(BF16))[0].dtype == BF16
    assert len(hc._bf16_parts(a.astype(BF16))) == 1
