"""The hyper-connected latent-attention sequence tower (four residual
streams mixed around every sublayer by Sinkhorn-projected maps, latent
attention whose keys are wider than its values under a YaRN rotary rule,
a gated dense feed-forward, gated sparse experts beside a shared expert)
against the benchmark's plain reference, at small widths on the CPU with
weights made from a seed.

The reference (``benchmarks/chip/reference_hyper_seq.py``) imports
nothing of ``persia_tpu``: the maps, the Sinkhorn rounds and the stream
mixing written out as einsums, the YaRN frequencies computed a pair at a
time, attention as the full score matrix, Adam written out.
"""

import hashlib
import importlib
import importlib.util
import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import check  # noqa: E402
import costs_hyper_seq as costs  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import reference_hyper_seq as ref  # noqa: E402
import weights_hyper_seq as weights  # noqa: E402
from placements import device_seq_hyper as placement  # noqa: E402

from persia_tpu import metrics, tracing  # noqa: E402
from persia_tpu.models import hybrid_seq  # noqa: E402
from persia_tpu.parallel.device_mode import (  # noqa: E402
    make_device_mode_trainer,
)
from persia_tpu.parallel.mesh import make_mesh  # noqa: E402
from persia_tpu.parallel.train import next_item_cross_entropy  # noqa: E402

F32 = jnp.float32
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
# keys of 16 + 8 = 24 beside values of 16, the rule on, four streams
SZ = {"pattern": "LDLE", "mtp_pattern": "LE", "mtp_depth": 0,
      "mtp_weight": 0.0, "hidden": 64, "vocab": 512, "eps": 1e-6,
      "heads": 4, "q_rank": 24, "kv_rank": 16, "nope_dim": 16,
      "rope_dim": 8, "v_dim": 16, "rope_theta": 1e4, "dense_width": 96,
      "experts_routed": 16, "experts_held": [0, 1, 2, 3],
      "experts_per_token": 2, "expert_width": 32, "shared_width": 32,
      "routed_scaling": 2, "streams": 4, "sinkhorn_iters": 20,
      "hyper_eps": 1e-6, "hyper_clamp": [-30, 30], "rope_scaling": YARN}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8}
CELL = "xing4.0-29b-a4b.device-histories8k"


def _config():
    path = os.path.join(BENCH_DIR, "configs", "xing4.0-29b-a4b.json")
    with open(path) as f:
        return json.load(f)


def _highest(f, *args):
    with jax.default_matmul_precision("highest"):
        return f(*args)


def _close(a, b, rtol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = np.abs(b).max() + 1e-30
    np.testing.assert_allclose(a / scale, b / scale, atol=rtol)


def _tower_params(leaves, sz=SZ):
    """The program's parameter tree out of the benchmark's leaves."""
    tree = {}
    for name, path in placement.leaf_paths(sz).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = jnp.copy(leaves[name])
    return tree


# --- the rotary rule ---------------------------------------------------------


def test_yarn_frequencies_and_softmax_scale_follow_the_written_rule():
    """d 64, base 1e4, factor 64 over 4096 positions: the pairs up to 10
    keep their frequency, those from 23 are divided by 64, a linear ramp
    between; the scores' factor is 192^(-1/2) (0.1 ln 64 + 1)^2. Program
    and reference alike, to float32's last digits (1e-6 relative)."""
    rule = placement.rotary_rule(YARN)
    d, base = 64, 1e4
    low = math.floor(d * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(d * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (10, 23)
    i = np.arange(32)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = base ** (-2 * i / d) * ((1 - ramp) + ramp / 64)
    got = hybrid_seq.yarn_frequencies(d, base, rule)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(got[:11], base ** (-2 * i[:11] / d),
                               rtol=1e-12)
    np.testing.assert_allclose(got[23:], base ** (-2 * i[23:] / d) / 64,
                               rtol=1e-12)
    freq, amplitude, softmax = ref.yarn(d, base, YARN)
    np.testing.assert_allclose(freq, want, rtol=1e-12)
    g = 0.1 * math.log(64) + 1
    assert g == pytest.approx(1.4159, abs=1e-4) and amplitude == 1.0
    assert softmax == pytest.approx(g * g)
    assert hybrid_seq.softmax_scale(192, rule) == pytest.approx(
        g * g / math.sqrt(192))
    assert hybrid_seq.softmax_scale(192, rule) * math.sqrt(192) == \
        pytest.approx(2.005, abs=1e-3)
    # with no rule, and under a rule that sets no mscale_all_dim: plain
    assert hybrid_seq.softmax_scale(256) == 1 / 16
    assert hybrid_seq.softmax_scale(
        256, rule._replace(mscale_all_dim=0.0)) == 1 / 16
    assert ref.yarn(8, 1e6, None) == ([1e6 ** (-i / 4) for i in range(4)],
                                      1.0, 1.0)


@pytest.mark.parametrize("rule", [None, YARN], ids=["plain", "yarn"])
def test_rotary_turns_each_pair_by_the_rule_s_frequency(rule):
    """Feature i and feature i + d/2 at position t, as one complex
    number, times exp(i t f_i); without a rule f_i = theta^(-2i/d) and
    ``rotary`` returns what it returned before it knew of rules, bit for
    bit. 1e-5 of the largest value: float32 cos and sin at t < 50."""
    theta = 1e4
    x = np.random.default_rng(0).normal(size=(2, 50, 3, 8))
    mine = placement.rotary_rule(rule)
    freq = (theta ** (-np.arange(4) / 4.0) if rule is None
            else hybrid_seq.yarn_frequencies(8, theta, mine))
    t = np.arange(50)[:, None, None]
    z = (x[..., :4] + 1j * x[..., 4:]) * np.exp(1j * t * freq)
    want = np.concatenate([z.real, z.imag], axis=-1)
    _close(hybrid_seq.rotary(jnp.asarray(x, F32), theta, mine), want, 1e-5)
    _close(ref.rotate(jnp.asarray(x, F32), *ref.yarn(8, theta, rule)[:2]),
           want, 1e-5)
    if rule is None:
        np.testing.assert_array_equal(
            np.asarray(hybrid_seq.rotary(jnp.asarray(x, F32), theta)),
            np.asarray(hybrid_seq.rotary(jnp.asarray(x, F32), theta, None)))
    else:   # the slow pairs turn less than the plain rule turns them
        plain = np.asarray(hybrid_seq.rotary(jnp.asarray(x, F32), theta))
        assert np.abs(plain - want).max() > 1e-2


# --- the Sinkhorn map --------------------------------------------------------


@pytest.mark.parametrize("logits", [
    np.full((4, 4), 30.0), np.full((4, 4), -30.0),
    60.0 * np.eye(4) - 30.0, 30.0 - 60.0 * np.eye(4),
    np.random.default_rng(1).normal(size=(6, 4, 4)) * 1.7,
], ids=["all_at_the_upper_clamp", "all_at_the_lower_clamp",
        "diagonal_up_rest_down", "diagonal_down_rest_up", "order_one"])
def test_the_sinkhorn_map_is_doubly_stochastic(logits):
    """Rows and columns sum to one. Columns within 2e-6 (the last thing
    a round does is divide them by their sum + 1e-6); rows within 1e-4:
    twenty rounds bring maps of order-one logits, and the extreme maps
    at the clamp's ends (uniform, or a permutation pattern), to float32's
    round-off, and 1e-4 leaves the eps in every denominator its room."""
    m = np.asarray(hybrid_seq.sinkhorn(jnp.asarray(logits, F32), 20, 1e-6))
    assert np.all(m >= 0) and np.all(np.isfinite(m))
    np.testing.assert_allclose(m.sum(axis=-2), 1.0, atol=2e-6)
    np.testing.assert_allclose(m.sum(axis=-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(
        m, np.asarray(ref.sinkhorn(jnp.asarray(logits, F32), 20, 1e-6)),
        atol=1e-6)


def test_the_sinkhorn_map_s_gradient_runs_through_every_round():
    """Against the reference's, differentiated as written: 1e-5 of the
    largest entry (the same float32 operations in the same order). A
    map that stopped the gradient at its last round would return the
    derivative of one normalisation, which is another matrix."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(5, 4, 4)) * 1.5, F32)
    w = jnp.asarray(rng.normal(size=(5, 4, 4)), F32)
    got = jax.grad(lambda x: jnp.sum(w * hybrid_seq.sinkhorn(x, 20, 1e-6)))(
        logits)
    want = jax.grad(lambda x: jnp.sum(w * ref.sinkhorn(x, 20, 1e-6)))(logits)
    _close(got, want, 1e-5)

    def last_round_only(x):
        m = jax.lax.stop_gradient(ref.sinkhorn(x, 19, 1e-6)) * jnp.exp(
            x - jax.lax.stop_gradient(x))
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + 1e-6)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + 1e-6)

    short = jax.grad(lambda x: jnp.sum(w * last_round_only(x)))(logits)
    assert np.abs(np.asarray(short - want)).max() > 1e-2 * np.abs(
        np.asarray(want)).max()


# --- the tower against the reference, float32 --------------------------------


def _reference_loss(leaves, rows, target, sz=SZ):
    qz = lambda v: v  # noqa: E731
    table = leaves["table"][rows]
    x = jnp.broadcast_to(table[:, :, None, :],
                         (*table.shape[:2], sz["streams"], sz["hidden"]))
    for i, kind in enumerate(sz["pattern"]):
        p = {name.split(".", 1)[1]: v for name, v in leaves.items()
             if name.startswith(f"L{i}.")}
        x = ref.layer(kind, p, x, sz, qz)
    return ref.top_loss({"final_norm": leaves["final_norm"],
                         "head": leaves["head"]}, x, target, sz, qz)


def test_the_float32_tower_and_its_gradients_match_the_reference():
    """Loss and every leaf's gradient, ``v_dim != nope_dim + rope_dim``
    and the YaRN rule on, four streams through LDLE: 2e-4 of each leaf's
    largest entry at ``highest`` precision (the kernel's blockwise
    softmax and the experts' sorted dispatch add in another order than
    the reference's full matrices)."""
    leaves = weights.make(11, SZ)
    rng = np.random.default_rng(3)
    seq = rng.integers(1, SZ["vocab"], size=(2, 41))
    rows, target = jnp.asarray(seq[:, :-1]), jnp.asarray(seq[:, 1:])
    tower = placement.build_tower(SZ, compute_dtype=F32)
    paths = placement.leaf_paths(SZ)

    def mine(leaves):
        logits = tower.apply(
            {"params": _tower_params(leaves)["tower"]}, [],
            [(leaves["table"][rows], jnp.ones(rows.shape, bool))])
        return next_item_cross_entropy(logits, target)

    got_loss, got = _highest(jax.value_and_grad(mine), leaves)
    want_loss, want = _highest(jax.value_and_grad(
        lambda leaves: _reference_loss(leaves, rows, target)), leaves)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-5)
    assert set(got) == set(paths)
    # over identical streams the first sublayer's read-out only scales a
    # normed input and its stream map mixes equals, and the last
    # sublayer's stream map is undone by the contraction (its columns
    # sum to one): no gradient but rounding, on either side, which is
    # why a map's parameters are leaves of their own
    dead = {f"L{i}.hyper_{m}_{p}" for i, m in ((0, "pre"), (0, "res"),
                                               (3, "res"))
            for p in ("phi", "bias", "scale")}
    live = float(jnp.linalg.norm(want["L0.hyper_post_phi"]))
    for name in paths:
        if name in dead:
            for side in (got, want):
                assert float(jnp.linalg.norm(side[name])) < 1e-3 * live, name
        else:
            _close(got[name], want[name])
    assert float(jnp.linalg.norm(want["L1.hyper_res_phi"])) > 1e-2 * live


def _summed_after_one_block(leaves, rows, target):
    """The reference with every stream set to the streams' mean after
    the first block."""
    qz = lambda v: v  # noqa: E731
    e = leaves["table"][rows]
    x = jnp.broadcast_to(e[:, :, None, :], (*e.shape[:2], 4, 64))
    for i, kind in enumerate(SZ["pattern"]):
        p = {n.split(".", 1)[1]: v for n, v in leaves.items()
             if n.startswith(f"L{i}.")}
        x = ref.layer(kind, p, x, SZ, qz)
        if i == 1:
            x = jnp.broadcast_to(x.mean(axis=2, keepdims=True), x.shape)
    return ref.top_loss({"final_norm": leaves["final_norm"],
                         "head": leaves["head"]}, x, target, SZ, qz)


@pytest.mark.parametrize("broken", ["static_maps", "summed_after_one_block",
                                    "no_rule"])
def test_what_the_seeded_weights_are_chosen_to_catch(broken):
    """The maps' input-dependent part is of order one under the seeded
    weights: with the maps left static (their scales zero), the streams
    summed after the first block, or plain rotary in the rule's place,
    the first gradients, put in the program's place, fail the cell's
    ``rehearsal_limits`` (the loss, near log(vocab) whatever the tower
    computes from random weights, is no such test)."""
    leaves = weights.make(5, SZ)
    seq = np.random.default_rng(4).integers(1, SZ["vocab"], size=(2, 65))
    rows, target = jnp.asarray(seq[:, :-1]), jnp.asarray(seq[:, 1:])

    def norms(loss, leaves):
        value, grads = _highest(jax.value_and_grad(
            lambda leaves: loss(leaves, rows, target)), leaves)
        norm = {n: float(jnp.linalg.norm(g)) for n, g in grads.items()}
        return {"losses": [float(value)], "grad_norm": norm,
                "change_norm": norm}

    want = norms(_reference_loss, leaves)
    if broken == "static_maps":
        got = norms(_reference_loss, {
            n: jnp.zeros_like(v) if n.endswith("_scale") else v
            for n, v in leaves.items()})
    elif broken == "no_rule":
        got = norms(lambda *a: _reference_loss(
            *a, sz=dict(SZ, rope_scaling=None)), leaves)
    else:
        got = norms(_summed_after_one_block, leaves)
    numbers, _ = check.compare(got, want)
    with open(os.path.join(BENCH_DIR, "cells", f"{CELL}.json")) as f:
        limits = json.load(f)["rehearsal_limits"]
    ok, compared = check.judge(numbers, limits)
    assert not ok, compared
    assert (numbers["grad_gap"] > 2 * limits["grad_gap"]
            or numbers["grad_gap_median"] > 2 * limits["grad_gap_median"])


def test_a_prediction_module_over_streams_is_refused():
    tower = placement.build_tower(SZ, mtp_depth=1)
    x = jnp.ones((1, 8, 64)), jnp.ones((1, 8), bool)
    with pytest.raises(ValueError, match="residual streams"):
        tower.init(jax.random.key(0), [], [x])


# --- the tower through the device-mode trainer ------------------------------


def _batches(n, histories=2, t=48, seed=0):
    rng = np.random.default_rng(seed)
    seq = rng.integers(0, SZ["vocab"] - 1, size=(n, histories, t + 1))
    return [(s[:, :-1], s[:, 1:]) for s in seq]


@pytest.fixture(scope="module")
def built():
    model = placement.build_model(SZ)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    sample = {placement.SLOT: jnp.ones((1, 32), jnp.int32)}
    tracing.enable_tracing(True)
    try:
        params, opt_state, step = make_device_mode_trainer(
            model, placement.build_optimizer(OPT), mesh, [], sample,
            loss_fn=next_item_cross_entropy)
        span = [s for s in tracing.default_collector().recent()
                if s.name == "trainer/build_device_step"][-1]
    finally:
        tracing.enable_tracing(False)
    return {"model": model, "mesh": mesh, "step": step, "span": span,
            "params": params, "opt_state": opt_state}


def _feed(items, targets):
    rows = reference.row_index(targets, SZ["vocab"], "hashed")
    return ({placement.SLOT: jnp.asarray(items + 1, jnp.int32)},
            jnp.asarray(rows, jnp.int32))


def test_three_trainer_steps_match_the_reference(built):
    """The loss, the first gradient (from Adam's first moment) and the
    change after three steps, through ``make_device_mode_trainer`` in
    bfloat16, against the float32 reference. The limits are bfloat16's
    at these widths (8 bits of mantissa through four sublayers and their
    maps), as the latent-attention tower's test has them; a float32
    program reads a hundred times lower (the test above)."""
    seed, batches = 17, _batches(3)
    leaves, paths = weights.make(seed, SZ), placement.leaf_paths(SZ)
    params = _tower_params(leaves)
    shape_of = lambda tree: jax.tree_util.tree_map(jnp.shape, tree)  # noqa: E731
    assert shape_of(params) == shape_of(built["params"])
    opt_state = jax.tree_util.tree_map(jnp.copy, built["opt_state"])
    prog = {"losses": []}
    with built["mesh"]:
        for k, (items, targets) in enumerate(batches, start=1):
            ids, label = _feed(items, targets)
            params, opt_state, loss = built["step"](params, opt_state, [],
                                                    ids, label)
            prog["losses"].append(float(loss))
            if k == 1:
                prog["grad_norm"] = {
                    n: float(jnp.linalg.norm(placement._get(
                        opt_state[0].mu, p))) / (1 - OPT["b1"])
                    for n, p in paths.items()}
    prog["change_norm"] = {
        n: float(jnp.linalg.norm(placement._get(params, p) - leaves[n]))
        for n, p in paths.items()}
    rows = [(reference.row_index(i, SZ["vocab"], "hashed"),
             reference.row_index(t, SZ["vocab"], "hashed"))
            for i, t in batches]
    theirs = ref.first_steps(SZ, OPT, lambda: weights.make(seed, SZ), rows)
    numbers, where = check.compare(prog, theirs)
    assert numbers["loss_gap"] < 5e-3, (numbers, where)
    assert numbers["grad_gap_median"] < 1e-2, (numbers, where)
    assert numbers["grad_gap"] < 0.1, (numbers, where)
    assert numbers["change_gap"] < 5e-2, (numbers, where)


def test_the_build_is_tagged_and_the_step_carries_its_scopes(built):
    tags = built["span"].tags
    assert tags["tower_layers"] == "LDLE"
    assert tags["residual_streams"] == 4 and tags["sinkhorn_iters"] == 20
    assert tags["key_width"] == 24 and tags["value_width"] == 16
    assert tags["mtp_depth"] == 0 and tags["expert_matrices"] == 3
    assert tags["attention_residuals_kept"] == 2     # the two `L` layers
    # every sublayer's connection runs ops/hyper_connection's kernels
    assert tags["hyper_fused_sublayers"] == 4
    gauges = metrics.default_registry()
    for name, value in (("residual_streams", 4), ("sinkhorn_iters", 20),
                        ("key_width", 24), ("value_width", 16),
                        ("tower_layers", 4), ("experts_held", 4),
                        ("attention_residuals_kept", 2),
                        ("hyper_fused_sublayers", 4)):
        assert gauges.gauge(f"device_mode_{name}").value == value
    ids, label = _feed(*_batches(1)[0])
    with built["mesh"]:
        text = built["step"].lower(built["params"], built["opt_state"], [],
                                   ids, label).as_text(debug_info=True)
    for scope in ("tower", "hyper_expand", "hyper_connection", "hyper_maps",
                  "hyper_mix", "hyper_contract", "latent_attention",
                  "latent_project", "rotary", "flash_attention", "dense_ffn",
                  "experts", "experts_route", "experts_grouped",
                  "experts_shared", "item_head", "optimizer"):
        assert f"{scope}/" in text or f"{scope})" in text, scope
    # the maps and the mixing inside the connection, the sublayer's own
    # scope beside it, the kernel innermost
    for nested in ("tower/hyper_expand", "tower/hyper_contract",
                   "layer_0/hyper_connection/hyper_maps",
                   "layer_0/hyper_connection/hyper_mix",
                   "layer_0/latent_attention/mixer/flash_attention",
                   "layer_3/hyper_connection/hyper_mix",
                   "layer_3/experts/mixer/experts_grouped"):
        assert nested in text, nested


# --- one stream is the tower it was -----------------------------------------

# the digest of the train step's lowered text at the accepted sequence
# cells' rehearsal sizes (interpreted Pallas bodies and all): a tower of
# one stream lowers to the program it lowered to before the tower knew of
# streams, unequal widths or rotary rules. A later change that alters the
# one-stream program on purpose records its own digests here. PR 36 did:
# `nn.remat` keeps the flash kernel's `out` and `lse`, so the recomputed
# layer's forward call is gone. Against the text recorded on the parent
# of PR 35 (`3b6b259c3d9e5c26`, `91aefdebbde77ab6`), value names made
# alike, that is all that differs: 526 lines fewer and 5 new in the first
# (one attention layer), 1578 fewer and 15 new in the second (three): gone
# the interpreted forward kernel with its padding and reshapes, and the
# layer's old barrier; new `lse`'s slice and two reshapes moved to the
# forward pass, the `reduce_precision` to bfloat16 that JAX puts on a kept
# `out`, and the barrier with the two kept arrays among its operands.
ONE_STREAM = {
    "nemotron-3-nano-30b-a3b.device-histories8k": "a543cfedb432b0b5",
    "glm-4.7-flash.device-histories8k": "453a82043b12ce2c",
}


@pytest.mark.parametrize("cell_name", sorted(ONE_STREAM))
def test_one_stream_lowers_to_the_parent_s_program(cell_name):
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    _, cell, config, _ = man.cell(cell_name)
    other = importlib.import_module(f"placements.{cell['placement']}")
    sz = other.weights.sizes_of(config, cell["rehearsal"]["tower"])
    model = other.build_model(sz)
    assert model.tower.residual_streams == 1
    assert model.tower.rope_scaling is None
    loss = (other.loss_of(sz) if hasattr(other, "loss_of")
            else next_item_cross_entropy)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    ids = {other.SLOT: jnp.ones((1, 128), jnp.int32)}
    params, opt_state, step = make_device_mode_trainer(
        model, other.build_optimizer(config["optimizer"]), mesh, [], ids,
        loss_fn=loss)
    with mesh:
        text = step.lower(params, opt_state, [], ids,
                          jnp.ones((1, 128), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        ONE_STREAM[cell_name]


# --- the configuration, its costs and its readers ----------------------------


def test_the_configuration_states_the_parameters_it_runs():
    config = _config()
    sz = weights.sizes_of(config)
    assert weights.parameters(sz) == config["parameters_as_run"] == 759346190
    assert sz["pattern"] == "LDLELELELE" and sz["experts_routed"] == 64
    assert sz["experts_held"] == list(range(8)) and sz["vocab"] == 16384
    assert (sz["streams"], sz["sinkhorn_iters"], sz["hyper_clamp"]) == (
        4, 20, [-30, 30])
    assert set(config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers", "n_routed_experts", "vocab_size"}
    tower = placement.build_tower(sz)
    assert tower.step_tags() == {
        "tower_layers": "LDLELELELE", "experts_held": tuple(range(8)),
        "experts_routed": 64, "expert_matrices": 3, "mtp_depth": 0,
        "residual_streams": 4, "sinkhorn_iters": 20, "key_width": 192,
        "value_width": 128, "attention_residuals_kept": 5,
        "hyper_fused_sublayers": 10, "kda_layers": 0, "kda_fused_layers": 0,
        "kda_heads": 0, "kda_chunk": 0, "attention_positions": 1,
        "selected_layers": 0, "index_fused_layers": 0, "select_topk": 0,
        "index_heads": 0, "expert_scoring": "sigmoid"}
    assert tower.rope_scaling == hybrid_seq.YarnRule(64, 4096, 32, 1, 1, 1)
    model = placement.build_model(sz)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), [],
                           {placement.SLOT: jnp.ones((1, 16), jnp.int32)}))
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    assert len(leaves) == len(weights.leaf_specs(sz))
    assert sum(int(np.prod(x.shape)) for x in leaves) == 759346190


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's row stands in the file unchanged but
    the five it lists as reduced, whose published values stand beside
    them; ``rope_scaling`` is copied whole."""
    config = _config()
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_nextn_predict_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 1, 0, 8, 16384)


def test_the_costs_are_the_issue_s_count():
    """580.9 M forward multiply-accumulates an event at 8192 positions,
    28.55 TFLOP a step, 36 % of it attention's scores and values."""
    sz = weights.sizes_of(_config())
    macs = costs.forward_macs_per_event(sz, 8192)
    total = sum(macs.values())
    assert total == pytest.approx(580.88e6, rel=1e-4)
    assert macs["latent_project"] == 5 * (28411136 - 768 - 512)
    assert macs["latent_attention"] == 5 * 32 * (192 + 128) * 8193 / 2
    assert macs["latent_attention"] / total == pytest.approx(0.361, abs=1e-3)
    assert macs["dense_ffn"] == 99090432
    assert macs["experts_shared"] == 4 * 11010048
    assert macs["experts_routed"] == 4 * (229376 + 0.5 * 11010048)
    assert macs["heads"] == 3584 * 16384
    assert macs["hyper_project"] == 10 * 14336 * 24
    assert macs["hyper_mix"] == 10 * 24 * 3584
    assert costs.train_flops_per_event(_config(), 8192) * 8192 == \
        pytest.approx(28.55e12, rel=1e-3)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"),
        os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reading(**more):
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    counters = {"routed_batches": 3}
    counters.update({f"routed_rows_layer_{i}": 3 * 4096 for i in range(4)})
    r = types.SimpleNamespace(
        trace={"steps": 10.0, "window_s": 5.0,
               "ops": [("flash_attention", 1.5), ("gmm", 0.2),
                       ("tgmm", 0.1), ("fusion:kOutput", 2.0)]},
        peaks=peaks, config=_config(), batch=8192, chips=1,
        counters=counters,
        env=types.SimpleNamespace(mix={"session_length": 8192}))
    for k, v in more.items():
        setattr(r, k, v)
    return r


def test_the_readers_count_the_widths_apart():
    r = _reading()
    sz = weights.sizes_of(r.config)
    # seven products a layer, four at 192 and three at 128: 1152 a unit
    least = 5 * 2 * 32 * 8192 * 8193 / 2 * 1152 / 197e12
    assert costs.flash_least_seconds(sz, 8192, 1, r.peaks) == \
        pytest.approx(least)
    assert least == pytest.approx(62.8e-3, rel=1e-3)
    flash = _reader("flash_roofline.xing4.0-29b-a4b")
    assert flash.read(r) == pytest.approx(100 * least * 10 / 1.5)
    assert flash.read(r) < 100
    r.trace = dict(r.trace, ops=[("fusion:kOutput", 2.0)])
    assert flash.read(r) is None and flash.read(_reading(trace=None)) is None
    r = _reading()
    at = costs.grouped_least_seconds(sz, [4096.0] * 4, r.peaks)
    assert at == pytest.approx(
        4 * 3 * 2 * 4096 * (3584 * 2048 + 1024 * 3584) / 197e12)
    grouped = _reader("grouped_roofline.xing4.0-29b-a4b")
    assert grouped.read(r) == pytest.approx(100 * at * 10 / 0.3)
    assert grouped.read(_reading(counters={})) is None
    assert _reader("mfu.xing4.0-29b-a4b").read(r) == pytest.approx(
        100 * costs.train_flops_per_event(r.config, 8192) * 8192 * 10 / 5.0
        / 197e12)
    # the streams' least time: 6.25 states of 8192 x 4 x 3584 bfloat16 a
    # sublayer over the HBM peak
    state = 8192 * 4 * 3584 * 2
    assert costs.hyper_least_seconds(sz, 8192, 1, r.peaks) == \
        pytest.approx(10 * 6.25 * state / 819e9)


def test_the_cell_is_in_the_manifest_with_its_three_readers():
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    assert man.validate()
    entry, cell, config, _ = man.cell(CELL)
    assert (entry["chips"], entry["traffic"], cell["placement"]) == (
        1, "histories8k", "device_seq_hyper")
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert {"mfu.xing4.0-29b-a4b", "flash_roofline.xing4.0-29b-a4b",
            "grouped_roofline.xing4.0-29b-a4b"} <= names
    assert set(cell["limits_why"]) >= set(cell["limits"])
