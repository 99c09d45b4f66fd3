"""The flash kernel's ``out`` and ``lse`` are kept across the sequence
tower's ``nn.remat``: the two residuals carry names
(``flash_attention.RESIDUAL_NAMES``), the tower's policy keeps those
names, and the recomputed layer no longer runs the kernel's forward.
Counted in the gradient's jaxpr, compared bit for bit with the default
policy, and, outside any ``jax.checkpoint``, compared with the text the
unnamed kernel lowers to. CPU, interpret mode, small widths."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu.models import hybrid_seq
from persia_tpu.ops import flash_attention as fa

F32 = jnp.float32
WIDTHS = dict(
    hidden=32, vocab=64, attn_heads=4, attn_kv_heads=2, attn_head_dim=16,
    latent_heads=2, latent_q_rank=16, latent_kv_rank=16, latent_nope_dim=16,
    latent_rope_dim=8, latent_v_dim=16, dense_width=32, compute_dtype=F32)
# the three forms a tower's attention layer takes, with its attention
# layers counted: grouped-query attention and latent attention under
# `_Layer` (the latter with a prediction module, whose last two layers
# are the pattern's again), latent attention under `_HyperLayer`
FORMS = {
    "grouped_query": (dict(pattern="*D"), 1),
    "latent": (dict(pattern="LDL", mtp_depth=1), 3),
    "latent_hyper": (dict(pattern="LD", residual_streams=4,
                          sinkhorn_iters=3), 1),
}
EVERY_FORM = pytest.mark.parametrize("form", sorted(FORMS))
KERNELS = ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel")


def _tower_loss(form):
    """A tower of ``form``, its parameters and a loss over them."""
    how, layers = FORMS[form]
    tower = hybrid_seq.HybridSequenceTower(**WIDTHS, **how)
    rows = jnp.asarray(
        np.random.default_rng(7).normal(size=(2, 40, WIDTHS["hidden"])), F32)
    inputs = [(rows, jnp.ones(rows.shape[:2], bool))]
    params = tower.init(jax.random.key(0), [], inputs)

    def loss(params):
        out = tower.apply(params, [], inputs)
        return sum(jnp.mean(y ** 2) for y in jax.tree_util.tree_leaves(out))

    return tower, params, loss, layers


def _keep_nothing(monkeypatch):
    """``nn.remat`` as the tower wrapped its layers until PR 36: the
    default policy, nothing kept but a layer's input."""
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)


def _pallas_kernels(jaxpr, found=None):
    """The kernel function's name of every ``pallas_call`` equation of
    ``jaxpr``, through the bodies of ``remat``, ``custom_vjp``, ``scan``,
    ``while``, ``cond`` and ``pjit`` equations."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["jaxpr"].debug_info.func_name)
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)     # a closed jaxpr's own
                if hasattr(sub, "eqns"):
                    _pallas_kernels(sub, found)
    return found


def _flash_calls(loss, params):
    kernels = _pallas_kernels(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    return [kernels.count(name) for name in KERNELS]


@EVERY_FORM
@pytest.mark.parametrize("forwards", [1, 2], ids=["kept", "default_policy"])
def test_the_gradient_s_kernel_calls_an_attention_layer(form, forwards,
                                                        monkeypatch):
    """Forward, dq, dk/dv once each; under the default policy the
    recomputed layer ran the forward a second time."""
    if forwards == 2:
        _keep_nothing(monkeypatch)
    tower, params, loss, layers = _tower_loss(form)
    assert tower.step_tags()["attention_residuals_kept"] == layers
    assert _flash_calls(loss, params) == [forwards * layers, layers, layers]


@EVERY_FORM
def test_loss_and_gradients_are_the_default_policy_s_bit_for_bit(
        form, monkeypatch):
    """Op by op, not under one ``jit``: there every primitive is a
    program of its own, as a Mosaic kernel is on the chip, and the kept
    ``out`` is the array the recomputation would have made. Inside one
    program XLA:CPU fuses an interpreted kernel's body with what stands
    around it, differently in the two programs (1e-9 of a gradient)."""
    _, params, loss, _ = _tower_loss(form)
    kept = jax.value_and_grad(loss)(params)
    _keep_nothing(monkeypatch)
    _, params, loss, _ = _tower_loss(form)
    rebuilt = jax.value_and_grad(loss)(params)
    flat = jax.tree_util.tree_flatten_with_path
    for (path, a), (_, b) in zip(flat(kept)[0], flat(rebuilt)[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_a_tower_without_attention_keeps_nothing():
    tower = hybrid_seq.HybridSequenceTower(**WIDTHS, pattern="MEDE")
    assert tower.step_tags()["attention_residuals_kept"] == 0


# --- outside a jax.checkpoint the names are the identity ----------------------


def _unnamed():
    """``flash_attention_masked`` as it was before its residuals carried
    names: the same kernels behind a ``custom_vjp`` of this test's
    making. ``mask`` is a (B, T_k) float array or None."""
    kw = dict(causal=True, block_q=None, block_k=None, interpret=True)

    @jax.custom_vjp
    def attend(q, k, v, mask):
        return fa.flash_attention_fwd_pallas(q, k, v, **kw, kv_mask=mask)

    def fwd(q, k, v, mask):
        out, lse = fa.flash_attention_fwd_pallas(
            q, k, v, **kw, return_lse=True, kv_mask=mask)
        return out, (q, k, v, out, lse, mask)

    def bwd(res, g):
        *res, mask = res
        grads = fa.flash_attention_bwd_pallas(*res, g, **kw, kv_mask=mask)
        return (*grads, None if mask is None else jnp.zeros_like(mask))

    attend.defvjp(fwd, bwd)
    return attend


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "kv_mask"])
def test_outside_a_checkpoint_the_gradient_lowers_as_the_unnamed_kernel_s(
        masked):
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 200, 16)), F32)
               for _ in range(3))
    mask = jnp.asarray(rng.random((1, 200)) < 0.8, F32) if masked else None

    def lowered(attend):
        def loss(q, k, v, mask):
            return jnp.mean(attend(q, k, v, mask) ** 2)
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, k, v, mask).as_text()
        # MLIR tells same-named private functions (`_pad`, `_where`)
        # apart by a number from a counter that the name operation moves
        # by one, though it lowers to nothing: number them by appearance
        seen = {}
        return re.sub(r"@\w+", lambda m: seen.setdefault(
            m.group(), f"@f{len(seen)}"), text)

    named = functools.partial(
        fa.flash_attention_masked, causal=True, interpret=True)
    assert lowered(_unnamed()) == lowered(
        lambda q, k, v, mask: named(q, k, v, kv_mask=mask))
    # and a policy that keeps the names finds them in the forward rule
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(named(q, k, v))))(q))
    for name in fa.RESIDUAL_NAMES:
        assert f"name={name}" in jaxpr, name
