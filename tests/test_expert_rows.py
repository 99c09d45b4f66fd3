"""The pair rows' kernels (``ops/expert_rows.py``), interpreted on the
CPU, against the plain gather and scatter-add through the whole
``dispatch_pairs`` and its written-out backward; and the one traced
callee a shape that keeps a step's first call cheap."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu.models import hybrid_seq
from persia_tpu.ops import expert_rows
from persia_tpu.ops.expert_rows import PairRows

F32, BF16 = jnp.float32, jnp.bfloat16
JIT = ("jit", "pjit")     # the primitive of a jax.jit call, by version
TOKENS, HIDDEN, HELD, ROUTED, WIDTH = 64, 128, 4, 32, 32


def _routing(per_token, held_of, seed):
    """``order`` padded to whole buffers and ``sizes`` where token ``t``
    chooses ``held_of[t]`` of the held experts and the rest of its
    ``per_token`` among those held elsewhere, in a random order."""
    rng = np.random.default_rng(seed)
    chosen = np.stack([np.concatenate([
        rng.permutation(HELD)[:h],
        HELD + rng.permutation(ROUTED - HELD)[:per_token - h]])[
            rng.permutation(per_token)] for h in held_of])
    local = np.where(chosen < HELD, chosen, HELD).reshape(-1)
    rows = hybrid_seq.pair_buffer_rows(TOKENS, per_token, HELD, ROUTED)
    order = np.argsort(local, kind="stable").astype(np.int32)
    return (jnp.asarray(np.pad(order, (0, -order.size % rows))),
            jnp.asarray(np.bincount(local, minlength=HELD + 1), jnp.int32),
            rows)


def _held_of(case, rows, seed):
    """Held pairs a token: none at all; one each (a part of the buffer);
    exactly the buffer's rows; half a buffer more, which takes a second
    pass."""
    if case in ("none", "partial"):
        return [int(case == "partial")] * TOKENS
    total = rows if case == "full" else rows + rows // 2
    held_of = [total // TOKENS] * TOKENS
    for t in np.random.default_rng(seed).permutation(TOKENS)[
            :total % TOKENS]:
        held_of[t] += 1
    return held_of


@pytest.mark.parametrize("per_token", [6, 8])
@pytest.mark.parametrize("activation", ["relu2", "swiglu"])
@pytest.mark.parametrize("case,passes", [
    ("none", 0), ("partial", 1), ("full", 1), ("two_passes", 2)])
def test_the_kernels_move_the_rows_the_plain_form_moves(case, passes,
                                                        activation,
                                                        per_token):
    """Output and the gradients to tokens, both matrices and the routing
    weights, with the kernels interpreted and with XLA's gather and
    scatter-add, are the same numbers: rows are copied and each token's
    sums added in the buffer's order. Rows past the held groups come out
    of the gather zero."""
    rows = hybrid_seq.pair_buffer_rows(TOKENS, per_token, HELD, ROUTED)
    assert PairRows.of(TOKENS, HIDDEN, rows).kernels
    order, sizes, rows = _routing(
        per_token, _held_of(case, rows, per_token), per_token)
    held = int(sizes[:-1].sum())
    assert -(-held // rows) == passes
    assert case != "full" or held == rows
    rng = np.random.default_rng(7)
    wider = hybrid_seq.ACTIVATIONS[activation].wider
    tokens = jnp.asarray(rng.normal(size=(TOKENS, HIDDEN)), BF16)
    w1 = jnp.asarray(rng.normal(size=(HELD, HIDDEN, wider * WIDTH)) * 0.1,
                     BF16)
    w2 = jnp.asarray(rng.normal(size=(HELD, WIDTH, HIDDEN)) * 0.1, BF16)
    weight = jnp.asarray(rng.random(TOKENS * per_token), F32)
    ct = jnp.asarray(rng.normal(size=(TOKENS, HIDDEN)), F32)

    def loss(tokens, w1, w2, weight):
        return jnp.sum(ct * hybrid_seq.dispatch_pairs(
            tokens, w1, w2, weight, order, sizes, rows, per_token,
            activation))

    def run(kernels, monkeypatch):
        with monkeypatch.context() as m:
            m.setattr(PairRows, "of",
                      classmethod(lambda cls, *shape: cls(kernels)))
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(
                tokens, w1, w2, weight)

    with pytest.MonkeyPatch.context() as mp:
        (mine, my_grads), (plain, plain_grads) = run(True, mp), run(False, mp)
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(plain))
    for got, want in zip(my_grads, plain_grads):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    assert passes == 0 or float(jnp.max(jnp.abs(my_grads[3]))) > 0

    # the first buffer as the kernel gathers it: the held rows, then zeros
    lo, part_sizes = hybrid_seq._buffers(order, sizes, rows)[1](0)
    token_of = order[:rows] // per_token
    moves = PairRows(True)
    buffer = moves.take(tokens, token_of, part_sizes)
    assert buffer.dtype == BF16
    buffer = np.asarray(buffer, np.float32)
    here = int(part_sizes[:-1].sum())
    assert here == min(held, rows)
    np.testing.assert_array_equal(
        buffer[:here], np.asarray(tokens, np.float32)[token_of[:here]])
    assert not buffer[here:].any()


def test_the_kernels_walk_several_column_slabs(monkeypatch):
    """A hidden width of three slabs (the cells' widths take two to four)
    gives what one slab gives, from a float32 source and a bfloat16 one
    widened in VMEM."""
    rng = np.random.default_rng(3)
    source = jnp.asarray(rng.normal(size=(48, 384)), F32)
    token_of = jnp.asarray(rng.integers(0, 48, 48), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(48, 384)), BF16)
    weight = jnp.asarray(rng.random(48), F32)
    acc = jnp.asarray(rng.normal(size=(48, 384)), F32)
    count = jnp.asarray([29], jnp.int32)

    def both():
        return (expert_rows.take_rows(source, token_of, count,
                                      interpret=True),
                expert_rows.take_rows(source.astype(BF16), token_of, count,
                                      interpret=True),
                expert_rows.add_rows(acc, rows, weight, token_of, count,
                                     interpret=True))

    whole = both()
    monkeypatch.setattr(expert_rows, "SLAB", 128)
    assert expert_rows._slab(384, 48) == 128
    expert_rows.take_rows.clear_cache()
    expert_rows.add_rows.clear_cache()
    sliced = both()
    expert_rows.take_rows.clear_cache()
    expert_rows.add_rows.clear_cache()
    for a, b in zip(whole, sliced):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    want = np.asarray(source)[np.asarray(token_of)]
    want[29:] = 0
    np.testing.assert_array_equal(np.asarray(whole[0]), want)


def _eqns(jaxpr, inside=()):
    """Every equation of a jaxpr and its sub-jaxprs, with the names of
    the ``jax.jit`` callees it lies in."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        here = inside + ((eqn.params["name"],)
                         if eqn.primitive.name in JIT else ())
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub, here)


def test_a_tower_gradient_calls_one_traced_callee_a_shape():
    """Four expert layers under ``nn.remat``, forward and backward: every
    gather and every sum of the pair rows is a call of a ``jax.jit``
    callee, one callee a distinct shape (the same traced jaxpr, so one
    lowering), and no pallas_call of theirs stands outside it."""
    tower = hybrid_seq.HybridSequenceTower(
        pattern="EEEE", hidden=HIDDEN, vocab=64, experts_routed=ROUTED,
        experts_held=tuple(range(HELD)), experts_per_token=6,
        expert_width=WIDTH, shared_width=0, expert_activation="swiglu",
        expert_scoring="softmax")
    rows = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 32, HIDDEN)), F32)
    params = tower.init(jax.random.PRNGKey(0), [], [(rows, None)])

    def loss(params, rows):
        return jnp.sum(tower.apply(params, [], [(rows, None)]))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, rows)
    entries = ("take_rows", "add_rows")
    callees, outside = {}, 0
    for eqn, inside in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name in JIT and eqn.params["name"] in entries:
            key = (eqn.params["name"],
                   tuple(str(v.aval) for v in eqn.invars),
                   tuple(str(v.aval) for v in eqn.outvars))
            callees.setdefault(key, []).append(eqn.params["jaxpr"])
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == "expert_rows"):
            outside += not (inside and inside[-1] in entries)
    assert outside == 0
    # forward: tokens gathered, products summed; backward: tokens and the
    # cotangent gathered, the pullback summed; four layers each
    calls = {name: sum(len(v) for k, v in callees.items() if k[0] == name)
             for name in entries}
    assert calls["take_rows"] >= 3 * 4 and calls["add_rows"] >= 2 * 4
    # a gather of bfloat16 rows and one of float32, one sum: three shapes,
    # each one traced jaxpr however many layers and passes call it
    assert len(callees) == 3
    for key, jaxprs in callees.items():
        assert len({id(j) for j in jaxprs}) == 1, key
