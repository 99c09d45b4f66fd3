"""Selected attention (the pattern letter ``S``): grouped-query attention
over the keys a learned indexer selects, its selection and its alignment
loss, and the flash kernels under a mask a (query, key).

The plain form is written out here, the whole ``(T, T)`` way in
``jax.numpy`` float32 at ``highest`` precision: every score of every
head at once, the selection by ``lax.top_k`` over each row's causal
scores, a masked softmax, the alignment loss as its formula reads. The
program works by tiles, finds a row's threshold by bisection and hands
the kernels an int8 mask; ``benchmarks/chip/reference_sparse_seq.py``
ranks by a stable sort (held to the same selections here;
``tests/test_sparse_seq_tower.py`` holds the tower to it).

Tolerances: 2e-4 of each array's largest entry, as the other towers'
tests have it (the kernel's blockwise softmax and the tiles' sums add in
another order than the plain form does).
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import reference_sparse_seq as ref  # noqa: E402
import weights_sparse_seq as weights  # noqa: E402
from placements import device_seq_sparse as placement  # noqa: E402

from persia_tpu.ops import sparse_select  # noqa: E402
from persia_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention_selected,
)

F32 = jnp.float32
SZ = {"pattern": "SE", "hidden": 64, "vocab": 256, "eps": 1e-6,
      "heads": 4, "kv_heads": 2, "head_dim": 16, "rope_theta": 1e7,
      "index_heads": 4, "index_dim": 16, "index_rope_dim": 8, "topk": 4,
      "index_tile": 8, "index_loss_weight": 1.0,
      "experts_routed": 16, "experts_held": [0, 1, 2, 3],
      "experts_per_token": 4, "expert_width": 32}


def _highest(f, *args):
    with jax.default_matmul_precision("highest"):
        return f(*args)


def _close(a, b, rtol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = np.abs(b).max() + 1e-30
    np.testing.assert_allclose(a / scale, b / scale, atol=rtol)


def _params(seed, sz):
    return {name.split(".", 1)[1]: v
            for name, v in weights.make(seed, sz).items()
            if name.startswith("L0.") and not name.endswith(".norm")}


def _mixer(sz):
    return placement.build_tower(sz, compute_dtype=F32)._mixer("S", 1.0)


def _turned(x, width, theta):
    """The first ``width`` features of ``x`` (batch, T, heads, d)
    rotated: the pair (i, i + width / 2) at position t by the angle ``t
    theta^(-2 i / width)``, written as a complex product."""
    t, half = x.shape[1], width // 2
    angle = (np.arange(t)[:, None]
             * theta ** (-np.arange(half, dtype=np.float64) / half))
    turn = jnp.asarray(np.exp(1j * angle)[None, :, None, :], jnp.complex64)
    z = lax.complex(x[..., :half], x[..., half:width]) * turn
    return jnp.concatenate([jnp.real(z), jnp.imag(z), x[..., width:]], -1)


def plain(p, u, sz):
    """(output, alignment loss, selection) of one ``S`` mixer over ``u``
    (batch, T, hidden), the whole (T, T) way."""
    bs, t, _ = u.shape
    heads, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    ih, idim, theta = sz["index_heads"], sz["index_dim"], sz["rope_theta"]

    def rms(x):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + sz["eps"])

    q = _turned(rms((u @ p["q_proj"]).reshape(bs, t, heads, hd))
                * p["q_norm"], hd, theta)
    k = _turned(rms((u @ p["k_proj"]).reshape(bs, t, kv, hd))
                * p["k_norm"], hd, theta)
    v = (u @ p["v_proj"]).reshape(bs, t, kv, hd)
    still = lax.stop_gradient(u)
    q_i = _turned((still @ p["index_q"]).reshape(bs, t, ih, idim),
                  sz["index_rope_dim"], theta)
    k_i = still @ p["index_k"]
    k_i = k_i - jnp.mean(k_i, -1, keepdims=True)
    k_i = rms(k_i) * p["index_k_scale"] + p["index_k_bias"]
    k_i = _turned(k_i[:, :, None], sz["index_rope_dim"], theta)[:, :, 0]
    w = still @ p["index_w"] / math.sqrt(ih * idim)
    index = jnp.einsum("bqh,bhqk->bqk", w, jax.nn.relu(
        jnp.einsum("bqhd,bkd->bhqk", q_i, k_i)))
    causal = jnp.tril(jnp.ones((t, t), bool))
    ranked = jnp.where(causal, jnp.where(index == 0, 0.0, index), -jnp.inf)
    best, at = lax.top_k(lax.stop_gradient(ranked), min(sz["topk"], t))
    select = jnp.sum(jax.nn.one_hot(at, t) * (best > -jnp.inf)[..., None],
                     axis=-2) > 0
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, heads // kv, 2))
    a = jax.nn.softmax(jnp.where(select[:, None], s / math.sqrt(hd),
                                 -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", a, jnp.repeat(v, heads // kv, 2))
    target = lax.stop_gradient(jnp.mean(a, axis=1))
    log_r = jax.nn.log_softmax(jnp.where(select, index, -jnp.inf), axis=-1)
    seen = target > 0
    loss = jnp.sum(jnp.where(
        seen, target * (jnp.log(jnp.where(seen, target, 1.0))
                        - jnp.where(seen, log_r, 0.0)), 0.0)) / (bs * t)
    return out.reshape(bs, t, heads * hd) @ p["o_proj"], loss, select


CASES = [pytest.param(dict(SZ, topk=4, index_tile=8), 2, 16,
                      id="top4_of_16"),
         pytest.param(dict(SZ, topk=32, index_tile=64), 1, 128,
                      id="top32_of_128"),
         pytest.param(dict(SZ, topk=16, index_tile=4), 3, 12,
                      id="every_causal_key_under_topk"),
         pytest.param(dict(SZ, topk=8, index_tile=512), 2, 40,
                      id="a_tile_of_the_whole_history")]


@pytest.mark.parametrize("sz,histories,t", CASES)
def test_the_mixer_and_every_gradient_match_the_plain_form(sz, histories, t):
    """Output, alignment loss, selection, and the gradient of every leaf
    and of the input, of ``sum(c * output) + 3 loss``."""
    p = _params(11, sz)
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(histories, t, 64)), F32)
    c = jnp.asarray(rng.normal(size=(histories, t, 64)), F32)
    mixer = _mixer(sz)

    def mine(p, u):
        (out, loss), sown = mixer.apply({"params": p}, u,
                                        mutable=["selections"])
        return out, loss, sown["selections"]["selected"][0]

    out, loss, select = _highest(jax.jit(mine), p, u)
    want_out, want_loss, want_select = _highest(
        jax.jit(lambda p, u: plain(p, u, sz)), p, u)
    np.testing.assert_array_equal(np.asarray(select) != 0,
                                  np.asarray(want_select))
    _close(out, want_out)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    assert float(loss) > 1e-3 or t <= 2

    def scalar(f):
        def g(p, u):
            out, loss = f(p, u)[:2]
            return jnp.sum(c * out) + 3.0 * loss
        return jax.jit(jax.grad(g, argnums=(0, 1)))

    got = _highest(scalar(mine), p, u)
    want = _highest(scalar(lambda p, u: plain(p, u, sz)), p, u)
    assert set(got[0]) == set(p)
    for name in p:
        _close(got[0][name], want[0][name])
        assert float(jnp.linalg.norm(want[0][name])) > 0, name
    _close(got[1], want[1])


def test_the_indexer_learns_from_its_loss_alone_and_nothing_else_does():
    """By construction: the cross entropy's side (the output) gives the
    indexer's leaves no gradient, the alignment loss gives none to any
    other leaf nor to the layer's input."""
    sz = dict(SZ, topk=6, index_tile=8)
    p = _params(5, sz)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 64)), F32)
    mixer = _mixer(sz)

    def part(which):
        return jax.jit(jax.grad(
            lambda p, u: jnp.sum(jnp.square(
                mixer.apply({"params": p}, u)[which])), argnums=(0, 1)))

    from_output, from_loss = (_highest(part(i), p, u) for i in (0, 1))
    for name in p:
        by_output = float(jnp.max(jnp.abs(from_output[0][name])))
        by_loss = float(jnp.max(jnp.abs(from_loss[0][name])))
        if name in weights.INDEXER:
            assert by_output == 0.0 and by_loss > 0.0, name
        else:
            assert by_output > 0.0 and by_loss == 0.0, name
    assert float(jnp.max(jnp.abs(from_loss[1]))) == 0.0
    assert float(jnp.max(jnp.abs(from_output[1]))) > 0.0


@pytest.mark.parametrize("t,topk,tile", [(16, 4, 8), (128, 32, 64),
                                         (96, 200, 32), (64, 1, 64)])
def test_every_row_selects_its_count_of_causal_keys(t, topk, tile):
    """``min(t + 1, topk)`` keys a query, none after it, and exactly the
    ones ``lax.top_k`` takes, on scores full of ties: small integers,
    signed zeros and runs of equal values at the cut."""
    rng = np.random.default_rng(t + topk)
    scores = rng.integers(-3, 4, size=(2, t, t)).astype(np.float32)
    scores[0, :, ::3] = -0.0
    scores[1, t // 2] = 1.0
    live = np.tril(np.ones((t, t), bool))
    count = np.broadcast_to(np.minimum(np.arange(t) + 1, topk), (2, t))
    select = np.asarray(sparse_select.select_top(
        jnp.asarray(scores), jnp.asarray(live), jnp.asarray(count,
                                                            jnp.int32)))
    assert select.dtype == np.int8
    np.testing.assert_array_equal(select.sum(-1), count)
    assert not select[:, ~live].any()
    ranked = np.where(live, np.where(scores == 0, 0.0, scores), -np.inf)
    best, at = lax.top_k(jnp.asarray(ranked), min(topk, t))
    want = np.zeros((2, t, t), bool)
    for b, q, j in zip(*np.nonzero(np.asarray(best) > -np.inf)):
        want[b, q, np.asarray(at)[b, q, j]] = True
    np.testing.assert_array_equal(select != 0, want)
    # and the ones the plain reference ranks first, by its stable sort
    np.testing.assert_array_equal(select != 0, np.asarray(ref.rank_select(
        jnp.asarray(scores), jnp.asarray(live), topk)))
    # the tiled entry over real index scores selects the same counts
    q_i = jnp.asarray(rng.normal(size=(2, t, 2, 8)), F32)
    k_i = jnp.asarray(rng.normal(size=(2, t, 8)), F32)
    w = jnp.asarray(rng.normal(size=(2, t, 2)), F32)
    tiled = np.asarray(sparse_select.select_keys(q_i, k_i, w, topk, tile))
    np.testing.assert_array_equal(tiled.sum(-1), count)
    assert not tiled[:, ~live].any()


def _plain_attention(q, k, v, select, scale):
    t = q.shape[2]
    keep = (select[:, None] != 0) & jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(keep, jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale,
                  -jnp.inf)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
            jax.nn.logsumexp(s, axis=-1))


@pytest.mark.parametrize("t,blocks,dk,dv", [
    (200, (128, 128), 64, 64), (256, (128, 256), 32, 32),
    (96, (None, None), 128, 128), (384, (256, 128), 48, 16)],
    ids=["padded_200", "unequal_blocks", "default_blocks",
         "keys_48_values_16"])
def test_the_flash_kernels_under_a_pair_mask_match_the_plain_form(
        t, blocks, dk, dv):
    """``flash_attention_selected`` against a dense masked softmax under
    a random mask a (query, key) shared by the heads, interpreted:
    ``out``, ``lse`` and the three gradients; entries above the diagonal
    attend nothing whatever the mask holds."""
    rng = np.random.default_rng(t)
    b, h = 2, 3
    q, k = (jnp.asarray(rng.normal(size=(b, h, t, dk)), F32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, h, t, dv)), F32)
    c = jnp.asarray(rng.normal(size=(b, h, t, dv)), F32)
    select = rng.random((b, t, t)) < 0.3      # above the diagonal too
    select |= np.eye(t, dtype=bool)
    select = jnp.asarray(select, jnp.int8)
    scale = 1.0 / math.sqrt(dk)

    def mine(q, k, v):
        return flash_attention_selected(q, k, v, select, *blocks)

    def theirs(q, k, v):
        return _plain_attention(q, k, v, select, scale)

    for got, want in zip(_highest(mine, q, k, v), _highest(theirs, q, k, v)):
        _close(got, want, 1e-5)
    grads = [_highest(jax.grad(lambda q, k, v: jnp.sum(c * f(q, k, v)[0]),
                               argnums=(0, 1, 2)), q, k, v)
             for f in (mine, theirs)]
    for got, want in zip(*grads):
        _close(got, want, 1e-5)
