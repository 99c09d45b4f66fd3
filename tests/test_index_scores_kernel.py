"""The index scores' two Pallas kernels (``ops/sparse_select.py``:
``index_scores`` under its ``jax.custom_vjp``), interpreted on the CPU,
against the plain form ``index_scores_plain`` and ``jax.vjp`` of it:
the scores and all three gradients at the cell's widths and at the
rehearsal's, for the first, a middle and the last tile of a history;
zeros after a tile's last query whatever the keys hold there; the plain
path where no block divides the shapes; and ``select_keys`` and
``alignment_loss`` over the kernels against the same over the plain
form.

Tolerances: float32 operands agree to float32 rounding (the kernels sum
the heads in another order); bfloat16 operands to 1e-2 of a gradient's
largest entry, since the pullback rounds ``ds`` to the operands' dtype
before its two products, as the flash kernels round theirs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu.ops import sparse_select

F32 = jnp.float32
# (index heads, width, tile, T, batch): the cell's indexer over a short
# history, the rehearsal's, one whose T only the 128-key block divides,
# and a tile of two blocks of queries
SHAPES = {"16x64": (16, 64, 512, 1024, 1), "4x16": (4, 16, 64, 256, 2),
          "2x32": (2, 32, 128, 384, 1), "2x16": (2, 16, 1024, 2048, 1)}


def _inputs(heads, width, tile, t, bs, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q_t = jnp.asarray(rng.normal(size=(bs, tile, heads, width)), dtype)
    k_i = jnp.asarray(rng.normal(size=(bs, t, width)), dtype)
    w_t = jnp.asarray(rng.normal(size=(bs, tile, heads)), F32)
    to_scores = jnp.asarray(rng.normal(size=(bs, tile, t)), F32)
    return q_t, k_i, w_t, to_scores


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return np.abs(got - want).max() / (np.abs(want).max() + 1e-30)


def _tiles(tile, t):
    return sorted({0, t // tile // 2, t // tile - 1})


CASES = [pytest.param(*SHAPES[name], i, dtype,
                      id=f"{name}_tile{i}_{jnp.dtype(dtype).name}")
         for name, dtype in (("16x64", jnp.bfloat16), ("4x16", F32),
                             ("4x16", jnp.bfloat16), ("2x32", F32),
                             ("2x16", F32))
         for i in _tiles(*SHAPES[name][2:4])]


@pytest.mark.parametrize("heads,width,tile,t,bs,i,dtype", CASES)
def test_the_kernels_match_the_plain_form_and_its_pullback(
        heads, width, tile, t, bs, i, dtype):
    assert sparse_select.index_blocks(tile, t) is not None
    q_t, k_i, w_t, to_scores = _inputs(heads, width, tile, t, bs, dtype, i)
    live = jnp.arange(t) < (i + 1) * tile   # the keys the tile may read

    def plain(q_t, k_i, w_t):
        return jnp.where(live, sparse_select.index_scores_plain(
            q_t, k_i, w_t), 0.0)

    got, back = jax.vjp(lambda *xs: sparse_select.index_scores(
        *xs, jnp.int32(i * tile)), q_t, k_i, w_t)
    want, plain_back = jax.vjp(plain, q_t, k_i, w_t)
    assert got.dtype == F32 and got.shape == (bs, tile, t)
    assert _gap(got, want) < 1e-6
    loose = 1e-6 if dtype == F32 else 1e-2
    for name, mine, theirs in zip(("q_i", "k_i", "w"), back(to_scores),
                                  plain_back(to_scores)):
        assert mine.dtype == theirs.dtype and mine.shape == theirs.shape
        # the weights' gradient multiplies nothing rounded: float32 both
        assert _gap(mine, theirs) < (1e-6 if name == "w" else loose), name


def test_without_a_position_every_key_block_is_walked():
    q_t, k_i, w_t, to_scores = _inputs(4, 16, 64, 256, 2, F32)
    got, back = jax.vjp(sparse_select.index_scores, q_t, k_i, w_t)
    want, plain_back = jax.vjp(sparse_select.index_scores_plain, q_t, k_i,
                               w_t)
    assert _gap(got, want) < 1e-6 and bool(jnp.any(got[..., -64:] != 0))
    for mine, theirs in zip(back(to_scores), plain_back(to_scores)):
        assert _gap(mine, theirs) < 1e-6


@pytest.mark.parametrize("i", [0, 1, 3])
def test_zeros_stand_after_the_tile_s_last_query_whatever_the_keys_hold(i):
    """A block of 256 keys holds four tiles of 64 queries: the keys
    after the tile's last query are NaN here, in the walked block too,
    and neither the scores nor a gradient see them."""
    heads, width, tile, t, bs = SHAPES["4x16"]
    q_t, k_i, w_t, to_scores = _inputs(heads, width, tile, t, bs, F32, 7)
    after = jnp.arange(t) >= (i + 1) * tile
    poisoned = jnp.where(after[None, :, None], jnp.nan, k_i)
    clean = jnp.where(after[None, :, None], 0.0, k_i)
    got, back = jax.vjp(lambda *xs: sparse_select.index_scores(
        *xs, jnp.int32(i * tile)), q_t, poisoned, w_t)
    want, clean_back = jax.vjp(lambda *xs: sparse_select.index_scores(
        *xs, jnp.int32(i * tile)), q_t, clean, w_t)
    assert not np.asarray(got)[..., (i + 1) * tile:].any()
    np.testing.assert_array_equal(got, want)
    for mine, theirs in zip(back(to_scores), clean_back(to_scores)):
        np.testing.assert_array_equal(mine, theirs)
    assert not np.asarray(back(to_scores)[1])[:, (i + 1) * tile:].any()


@pytest.mark.parametrize("tile,t", [(50, 200), (64, 320), (8, 256),
                                    (520, 1040)])
def test_shapes_no_block_divides_take_the_plain_path(tile, t):
    """A T that 128 does not divide, or a tile that is no multiple of
    16 (or no multiple of 512 past it): the plain form,
    bit for bit, and no Pallas call in the program."""
    assert sparse_select.index_blocks(tile, t) is None
    q_t, k_i, w_t, _ = _inputs(2, 8, tile, t, 1, F32)

    def scores(*xs):
        return sparse_select.index_scores(*xs, jnp.int32(0))

    assert "pallas_call" not in str(jax.make_jaxpr(scores)(q_t, k_i, w_t))
    np.testing.assert_array_equal(
        scores(q_t, k_i, w_t),
        sparse_select.index_scores_plain(q_t, k_i, w_t))


def test_the_blocks_follow_the_shapes():
    assert sparse_select.index_blocks(512, 8192) == (512, 512)
    assert sparse_select.index_blocks(64, 256) == (64, 256)
    assert sparse_select.index_blocks(128, 384) == (128, 128)
    assert sparse_select.index_blocks(1024, 2048) == (512, 512)
    assert sparse_select.query_block(512) == 512
    assert sparse_select.query_block(8) is None


# (T, topk, tile, index heads, width, histories): the cases of
# tests/test_selected_attention.py whose shapes a block divides, the
# rehearsal's, and one on the plain path
LAYERS = [pytest.param(128, 32, 64, 4, 16, 1, id="top32_of_128"),
          pytest.param(256, 64, 64, 4, 16, 2, id="the_rehearsal_s"),
          pytest.param(384, 200, 128, 2, 32, 1, id="top200_of_384"),
          pytest.param(40, 8, 8, 4, 16, 2, id="plain_top8_of_40")]


@pytest.mark.parametrize("t,topk,tile,ih,idim,bs", LAYERS)
def test_selection_and_alignment_loss_are_the_plain_form_s(
        t, topk, tile, ih, idim, bs, monkeypatch):
    """``select_keys`` and ``alignment_loss`` (loss and its three
    gradients) over the kernels against the same functions over the
    plain form, float32 operands: the same selection but for keys at a
    cut whose scores differ in float32's last digits, the same loss and
    gradients to float32 rounding."""
    heads, kv, hd = 4, 2, 16
    rng = np.random.default_rng(t + topk)

    def normal(*sizes):
        return jnp.asarray(rng.normal(size=sizes), F32)

    q_i, k_i = normal(bs, t, ih, idim), normal(bs, t, idim)
    w = normal(bs, t, ih) * (ih * idim) ** -0.5
    q, k = normal(bs, heads, t, hd), normal(bs, kv, t, hd)

    def selection(q_i, k_i, w):
        return sparse_select.select_keys(q_i, k_i, w, topk, tile)

    def loss_and_gradients(q_i, k_i, w, select):
        keep = (select != 0)[:, None]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(
            k, heads // kv, axis=1)) * hd ** -0.5
        lse = jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)

        def loss(q_i, k_i, w):
            return sparse_select.alignment_loss(q_i, k_i, w, q, k, lse,
                                                select, hd ** -0.5, tile)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q_i, k_i, w)

    select = jax.jit(selection)(q_i, k_i, w)
    loss, grads = jax.jit(loss_and_gradients)(q_i, k_i, w, select)
    monkeypatch.setattr(
        sparse_select, "index_scores",
        lambda q_t, k_i, w_t, start=None:
            sparse_select.index_scores_plain(q_t, k_i, w_t))
    want_select = jax.jit(selection)(q_i, k_i, w)
    want_loss, want_grads = jax.jit(loss_and_gradients)(q_i, k_i, w, select)
    count = np.minimum(np.arange(t) + 1, topk)
    np.testing.assert_array_equal(np.asarray(select).sum(-1),
                                  np.broadcast_to(count, (bs, t)))
    assert not np.triu(np.asarray(select), 1).any()
    assert np.mean(np.asarray(select) != np.asarray(want_select)) < 1e-4
    assert float(loss) > 0 and _gap(loss, want_loss) < 1e-6
    for mine, theirs in zip(grads, want_grads):
        assert _gap(mine, theirs) < 1e-5
