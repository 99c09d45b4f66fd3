"""The latent-attention sequence tower (latent attention with decoupled
rotary keys, a gated dense feed-forward, gated sparse experts beside a
shared expert, one multi-token-prediction module) against the
benchmark's plain reference, at small widths on the CPU with weights
made from a seed.

The reference (``benchmarks/chip/reference_latent_seq.py``) imports
nothing of ``persia_tpu``: the rotary rotation written out, attention as
the full score matrix, the experts as a loop under a dense mask, the
prediction module over positions 0..T-2 without a roll, Adam written
out.
"""

import importlib.util
import json
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
import costs_latent_seq as costs  # noqa: E402
import reference  # noqa: E402
import reference_latent_seq as ref  # noqa: E402
import weights_latent_seq as weights  # noqa: E402
from placements import device_seq_latent as placement  # noqa: E402

from persia_tpu import metrics, tracing  # noqa: E402
from persia_tpu.models import hybrid_seq  # noqa: E402
from persia_tpu.parallel.device_mode import (  # noqa: E402
    make_device_mode_trainer,
)
from persia_tpu.parallel.mesh import make_mesh  # noqa: E402
from persia_tpu.parallel.train import (  # noqa: E402
    next_item_cross_entropy,
    next_items_cross_entropy,
)

F32 = jnp.float32
SZ = {"pattern": "LDLE", "mtp_pattern": "LE", "mtp_depth": 1,
      "mtp_weight": 0.3, "hidden": 64, "vocab": 512, "eps": 1e-5,
      "heads": 4, "q_rank": 24, "kv_rank": 16, "nope_dim": 24,
      "rope_dim": 8, "v_dim": 32, "rope_theta": 1e6, "dense_width": 96,
      "experts_routed": 16, "experts_held": [0, 1, 2, 3],
      "experts_per_token": 2, "expert_width": 32, "shared_width": 32,
      "routed_scaling": 1.8}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8}


def _config():
    with open(os.path.join(BENCH_DIR, "configs", "glm-4.7-flash.json")) as f:
        return json.load(f)


def _leaves(seed, sz=SZ):
    return weights.make(seed, sz)


def _layer_params(leaves, i, sz=SZ):
    return {p: leaves[f"L{i}.{p}"]
            for p, _, _ in weights.layer_leaves(sz["pattern"][i], sz)}


def _highest(f, *args):
    with jax.default_matmul_precision("highest"):
        return f(*args)


def _close(a, b, rtol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = np.abs(b).max() + 1e-30
    np.testing.assert_allclose(a / scale, b / scale, atol=rtol)


def _mixer(kind, held=SZ["experts_held"]):
    """One float32 mixer of the tower, holding the experts ``held``."""
    return placement.build_tower(dict(SZ, experts_held=list(held)),
                                 compute_dtype=F32)._mixer(kind, 1.0)


# --- rotary -----------------------------------------------------------------


@pytest.mark.parametrize("theta", [1e6, 1e4])
def test_rotary_is_a_complex_rotation_of_each_pair(theta):
    """Feature i and feature i + d/2 at position t, as one complex
    number, times exp(i t theta^(-2i/d)); program and reference alike."""
    x = np.random.default_rng(0).normal(size=(2, 50, 3, 8))
    t = np.arange(50)[:, None, None]
    turn = np.exp(1j * t * theta ** (-np.arange(4) / 4.0))
    z = (x[..., :4] + 1j * x[..., 4:]) * turn
    want = np.concatenate([z.real, z.imag], axis=-1)
    _close(hybrid_seq.rotary(jnp.asarray(x, F32), theta), want, 1e-5)
    _close(ref.rotate(jnp.asarray(x, F32), theta), want, 1e-5)
    # position 0 is left as it is, and a rotation keeps each pair's norm
    got = np.asarray(hybrid_seq.rotary(jnp.asarray(x, F32), theta))
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(got[..., :4] ** 2 + got[..., 4:] ** 2,
                               x[..., :4] ** 2 + x[..., 4:] ** 2, rtol=1e-4)


# --- each mixer, forward and gradients --------------------------------------


@pytest.mark.parametrize("kind,t", [("L", 40), ("D", 24), ("E", 40)],
                         ids=["latent_attention", "dense_ffn", "experts"])
def test_a_mixer_and_its_gradients_match_the_reference(kind, t):
    i = SZ["pattern"].index(kind)
    p = _layer_params(_leaves(11), i)
    u = jnp.asarray(np.random.default_rng(3).normal(size=(2, t, 64)), F32)
    w = jnp.asarray(np.random.default_rng(4).normal(size=(2, t, 64)), F32)
    mixer = _mixer(kind)

    def mine(p, u):
        return mixer.apply({"params": p}, u)

    def theirs(p, u):
        return ref.MIXERS[kind](p, u, SZ, lambda v: v)

    _close(_highest(mine, p, u), _highest(theirs, p, u))
    got = _highest(jax.grad(lambda p, u: jnp.sum(w * mine(p, u)),
                            argnums=(0, 1)), p, u)
    want = _highest(jax.grad(lambda p, u: jnp.sum(w * theirs(p, u)),
                             argnums=(0, 1)), p, u)
    for name in p:
        _close(got[0][name], want[0][name])
    _close(got[1], want[1])


def test_latent_attention_reads_position_and_nothing_ahead():
    """The mask is causal (a later change reaches no earlier output) and
    the rotary key carries position (the same keys in another order
    give another output)."""
    p = _layer_params(_leaves(5), 0)
    mixer = _mixer("L")
    u = jnp.asarray(np.random.default_rng(1).normal(size=(1, 32, 64)), F32)
    out = _highest(lambda: mixer.apply({"params": p}, u))
    later = u.at[:, 20:].set(0.5)
    out2 = _highest(lambda: mixer.apply({"params": p}, later))
    np.testing.assert_allclose(out[:, :20], out2[:, :20], atol=1e-6)
    assert np.abs(np.asarray(out[:, 20:] - out2[:, 20:])).max() > 1e-3
    # two earlier positions swapped: without positions the last output,
    # a sum over the keys it sees, would not change
    swapped = u.at[:, 3].set(u[:, 11]).at[:, 11].set(u[:, 3])
    out3 = _highest(lambda: mixer.apply({"params": p}, swapped))
    assert np.abs(np.asarray(out[:, -1] - out3[:, -1])).max() > 1e-4


@pytest.mark.parametrize("v_dim", [16, 48])
def test_value_heads_of_another_width_match_the_reference(v_dim):
    """Keys of 24 + 8 beside values of 16 or 48 (the kernel takes the
    two widths apart since PR 35; until then the layer refused them):
    the output and the gradient of every leaf against the reference's
    full score matrix, 2e-4 of the largest entry as above."""
    sz = dict(SZ, v_dim=v_dim)
    p = _layer_params(_leaves(13, sz), 0, sz)
    assert p["kv_b"].shape == (16, 4 * (24 + v_dim))
    assert p["o_proj"].shape == (4 * v_dim, 64)
    u = jnp.asarray(np.random.default_rng(6).normal(size=(2, 40, 64)), F32)
    w = jnp.asarray(np.random.default_rng(7).normal(size=(2, 40, 64)), F32)
    mixer = placement.build_tower(sz, compute_dtype=F32)._mixer("L", 1.0)

    def mine(p, u):
        return mixer.apply({"params": p}, u)

    def theirs(p, u):
        return ref.latent_attention(p, u, sz, lambda v: v)

    _close(_highest(mine, p, u), _highest(theirs, p, u))
    got = _highest(jax.grad(lambda p, u: jnp.sum(w * mine(p, u)),
                            argnums=(0, 1)), p, u)
    want = _highest(jax.grad(lambda p, u: jnp.sum(w * theirs(p, u)),
                             argnums=(0, 1)), p, u)
    for name in p:
        _close(got[0][name], want[0][name])
    _close(got[1], want[1])


# --- the share: what one chip of an expert-parallel job computes ------------


@pytest.mark.parametrize("routed,per_token,scaling", [
    (16, 2, 1.8),       # this tower's rehearsal router
    (64, 4, 2),         # the hyper-connected tower's: 64 routed, top 4
], ids=["16_routed_top_2", "64_routed_top_4"])
def test_the_eight_shares_of_a_gated_layer_add_up_to_the_uncut_layer(
        routed, per_token, scaling):
    """The routed experts in 8 shares: the eight shares' routed parts,
    with the shared expert counted once, are the uncut reference layer,
    and every (token, expert) pair is routed to one share."""
    sz = dict(SZ, experts_routed=routed, experts_per_token=per_token,
              routed_scaling=scaling, experts_held=list(range(routed)))
    whole = _layer_params(_leaves(21, sz), 3, sz)
    u = jnp.asarray(np.random.default_rng(8).normal(size=(2, 40, 64)), F32)
    want = _highest(lambda: ref.experts(whole, u, sz, lambda v: v,
                                        held=list(range(routed))))
    shared = _highest(lambda: ref.shared_expert(
        whole, u.reshape(-1, 64), lambda v: v)).reshape(u.shape)
    total, rows = 0.0, 0
    for first in range(0, routed, routed // 8):
        ids = list(range(first, first + routed // 8))
        part = dict(whole, w1=whole["w1"][np.asarray(ids)],
                    w2=whole["w2"][np.asarray(ids)])
        mixer = placement.build_tower(dict(sz, experts_held=ids),
                                      compute_dtype=F32)._mixer("E", 1.0)
        out, state = _highest(lambda: mixer.apply(
            {"params": part}, u, mutable=["intermediates"]))
        total = total + (out - shared)
        rows += int(np.sum(state["intermediates"]["routed_rows"][0]))
    _close(total + shared, want)
    assert rows == 2 * 40 * per_token       # every pair, once


# --- the prediction module ---------------------------------------------------


def _tower_params(leaves, sz=SZ):
    """The tower's own subtree of the program's parameters."""
    tree = {}
    for name, path in placement.leaf_paths(sz).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = jnp.copy(leaves[name])
    return tree


def test_the_module_s_logit_at_t_reads_items_up_to_t_plus_one():
    """Position t of the module's output reads the hidden state at t and
    the row of item t+1: items after t+1 do not reach it, item t+1 does;
    the main head's output at t reads items up to t."""
    leaves = _leaves(7)
    tower = placement.build_tower(SZ, compute_dtype=F32)
    params = _tower_params(leaves)["tower"]
    items = np.random.default_rng(2).integers(1, 512, size=(1, 24))
    other = items.copy()
    other[:, 14:] = (other[:, 14:] * 7 + 3) % 511 + 1   # items 14.. change

    @jax.jit
    def apply(rows):
        return tower.apply({"params": params}, [],
                           [(rows, jnp.ones(rows.shape[:2], bool))])

    def run(ids):
        return _highest(apply, leaves["table"][jnp.asarray(ids)])

    (main, ahead), (main2, ahead2) = run(items), run(other)
    assert main.shape == ahead.shape == (1, 24, 512)
    assert main.dtype == ahead.dtype == F32
    # t <= 12: t + 1 <= 13 is unchanged
    np.testing.assert_allclose(ahead[:, :13], ahead2[:, :13], atol=1e-5)
    assert np.abs(np.asarray(ahead[:, 13] - ahead2[:, 13])).max() > 1e-3
    np.testing.assert_allclose(main[:, :14], main2[:, :14], atol=1e-5)
    assert np.abs(np.asarray(main[:, 14] - main2[:, 14])).max() > 1e-3


def test_the_module_shares_the_main_head_and_the_item_table():
    """No head of the module's own: its parameters are two input norms,
    the merge, one block and a norm before the shared head."""
    tower = placement.build_tower(SZ)
    x = jnp.ones((1, 8, 64)), jnp.ones((1, 8), bool)
    params = tower.init(jax.random.key(0), [], [x])["params"]
    assert set(params["mtp"]) == {"embed_norm", "hidden_norm", "merge",
                                  "layer_0", "layer_1", "head_norm"}
    assert params["mtp"]["merge"].shape == (128, 64)
    assert params["item_head"].shape == (64, 512)
    # with no module the tower is today's: one output, no such subtree
    plain = placement.build_tower(dict(SZ, mtp_depth=0))
    assert "mtp" not in plain.init(jax.random.key(0), [], [x])["params"]
    with pytest.raises(ValueError, match="one prediction module or none"):
        placement.build_tower(dict(SZ, mtp_depth=2)).init(
            jax.random.key(0), [], [x])


def test_the_second_target_is_the_first_shifted_and_the_last_left_out():
    rng = np.random.default_rng(0)
    main = jnp.asarray(rng.normal(size=(2, 5, 11)), F32)
    ahead = jnp.asarray(rng.normal(size=(2, 5, 11)), F32)
    target = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], jnp.int32)
    logp = np.asarray(jax.nn.log_softmax(ahead))
    # position t of the module against target[t + 1] = item t + 2
    want = -np.mean([logp[b, t, target[b, t + 1]] for b in range(2)
                     for t in range(4)])
    got = next_items_cross_entropy((main, ahead), target, ahead_weight=0.3)
    np.testing.assert_allclose(
        float(got), float(next_item_cross_entropy(main, target)) + 0.3 * want,
        rtol=1e-6)
    # the last position's logits of the module reach nothing
    changed = ahead.at[:, -1].set(100.0)
    assert float(next_items_cross_entropy((main, changed), target,
                                          ahead_weight=0.3)) == float(got)
    # a position whose next event is unknown has no event after next
    cut = target.at[0, 2].set(-1)
    shifted = jnp.asarray([[2, -1, 4, 5, -1], [7, 8, 9, 10, -1]], jnp.int32)
    np.testing.assert_allclose(
        float(next_items_cross_entropy((main, ahead), cut, ahead_weight=1.0)),
        float(next_item_cross_entropy(main, cut)
              + next_item_cross_entropy(ahead, shifted)), rtol=1e-6)


# --- the tower through the device-mode trainer ------------------------------


def _batches(n, histories=2, t=48, seed=0):
    rng = np.random.default_rng(seed)
    seq = rng.integers(0, SZ["vocab"] - 1, size=(n, histories, t + 1))
    return [(s[:, :-1], s[:, 1:]) for s in seq]


@pytest.fixture(scope="module")
def built():
    """The tower through ``DeviceModeModel`` and
    ``make_device_mode_trainer``, built once, with the build's span."""
    model = placement.build_model(SZ)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    sample = {placement.SLOT: jnp.ones((1, 32), jnp.int32)}
    tracing.enable_tracing(True)
    try:
        params, opt_state, step = make_device_mode_trainer(
            model, placement.build_optimizer(OPT), mesh, [], sample,
            loss_fn=placement.loss_of(SZ))
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
    """Both heads' loss, the first gradient (from Adam's first moment)
    and the change after three steps, through
    ``make_device_mode_trainer`` in bfloat16, against the float32
    reference."""
    seed, batches = 17, _batches(3)
    leaves, paths = _leaves(seed), placement.leaf_paths(SZ)
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
    theirs = ref.first_steps(SZ, OPT, lambda: _leaves(seed), rows)
    numbers, where = check.compare(prog, theirs)
    assert numbers["loss_gap"] < 5e-3, (numbers, where)
    assert numbers["grad_gap_median"] < 1e-2, (numbers, where)
    assert numbers["grad_gap"] < 0.1, (numbers, where)
    assert numbers["change_gap"] < 2e-2, (numbers, where)
    # the module's share of the loss is in it: the main head alone reads
    # about log(vocab), both about 1.3 times that
    assert theirs["losses"][0] > 1.2 * np.log(SZ["vocab"])


def test_the_step_carries_its_scopes(built):
    assert built["span"].tags["dense_update_tables"] == 1   # Adam: dense
    assert metrics.default_registry().gauge(
        "device_mode_dense_update_tables").value == 1
    ids, label = _feed(*_batches(1)[0])
    with built["mesh"]:
        text = built["step"].lower(built["params"], built["opt_state"], [],
                                   ids, label).as_text(debug_info=True)
    for scope in ("tables_gather", "tower", "latent_attention",
                  "latent_project", "rotary", "flash_attention", "dense_ffn",
                  "experts", "experts_route", "experts_grouped",
                  "experts_shared", "item_head", "mtp", "mtp_merge",
                  "mtp_head", "optimizer"):
        assert f"{scope}/" in text or f"{scope})" in text, scope
    # nested as PERF.md has them: the kernel innermost, and the module's
    # attention and experts under its own scope
    for nested in ("latent_attention/mixer/latent_project",
                   "latent_attention/mixer/rotary",
                   "latent_attention/mixer/flash_attention",
                   "tower/mtp/mtp/mtp_merge", "tower/mtp/mtp/mtp_head",
                   "mtp/layer_0/latent_attention/mixer/flash_attention",
                   "mtp/layer_1/experts/mixer/experts_grouped"):
        assert nested in text, nested


def test_routed_rows_puts_the_module_s_expert_layer_last(built):
    ids, _ = _feed(*_batches(1)[0])
    params = _tower_params(_leaves(3))
    @jax.jit
    def probe(p, i):
        _, state = built["model"].apply({"params": p}, [], i, train=False,
                                        mutable=["intermediates"])
        return (hybrid_seq.routed_rows(built["model"], p, [], i),
                state["intermediates"]["tower"])

    rows, sown = probe(params, ids)
    rows = np.asarray(rows)
    assert rows.shape == (2, 4) and rows.sum() > 0
    np.testing.assert_array_equal(
        rows[0], sown["layer_3"]["mixer"]["routed_rows"][0])
    np.testing.assert_array_equal(
        rows[1], sown["mtp"]["layer_1"]["mixer"]["routed_rows"][0])
    assert (rows[0] != rows[1]).any()


def test_the_configuration_states_the_parameters_it_runs():
    """The benchmark's configuration of this tower: the cut it lists and
    the parameter count it states are what its sizes come to, and what
    the program's tower declares."""
    config = _config()
    sz = weights.sizes_of(config)
    assert weights.parameters(sz) == config["parameters_as_run"] == 706518528
    assert sz["pattern"] == "LDLELELELE" and sz["experts_routed"] == 64
    assert sz["experts_held"] == list(range(8)) and sz["vocab"] == 19360
    assert set(config["reduced"]) == {"num_hidden_layers",
                                      "n_routed_experts", "vocab_size"}
    model = placement.build_model(sz)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), [],
                           {placement.SLOT: jnp.ones((1, 16), jnp.int32)}))
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    assert len(leaves) == len(weights.leaf_specs(sz)) == 88
    assert sum(int(np.prod(x.shape)) for x in leaves) == 706518528


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's row stands in the file unchanged but
    the three it lists as reduced, whose published values stand beside
    them."""
    config = _config()
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 19360)


def test_the_costs_are_the_issue_s_count():
    """604 M forward multiply-accumulates an event at 8192 positions,
    29.7 TFLOP a step, 63 % of it latent attention."""
    sz = weights.sizes_of(_config())
    macs = costs.forward_macs_per_event(sz, 8192)
    assert sum(macs.values()) == pytest.approx(604.27e6, rel=1e-4)
    assert macs["latent_project"] == 6 * (21759232 - 768 - 512)
    assert macs["latent_attention"] == 6 * 20 * 512 * 8193 / 2
    assert macs["dense_ffn"] == 62914560
    assert macs["experts_shared"] == 5 * 9437184
    assert macs["experts_routed"] == 5 * (131072 + 0.5 * 9437184)
    assert macs["heads"] == 2 * 2048 * 19360 + 8388608
    assert costs.train_flops_per_event(_config(), 8192) * 8192 == \
        pytest.approx(29.70e12, rel=1e-3)


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
    counters.update({f"routed_rows_layer_{i}": 3 * 4096 for i in range(5)})
    r = types.SimpleNamespace(
        trace={"steps": 10.0, "window_s": 7.5,
               "ops": [("flash_attention", 3.0), ("gmm", 0.2),
                       ("tgmm", 0.1), ("fusion:kOutput", 2.0)]},
        peaks=peaks, config=_config(), batch=8192, chips=1,
        counters=counters,
        env=types.SimpleNamespace(mix={"session_length": 8192}))
    for k, v in more.items():
        setattr(r, k, v)
    return r


def test_flash_roofline_is_seven_products_a_layer_over_the_group_s_time():
    r = _reading()
    # 7 products of 20 x 8192 x 8193 / 2 x 256 MACs over 197 TFLOP/s, in
    # six layers: the MXU binds (the bytes would take 1 ms)
    least = 6 * 7 * 2 * 20 * 8192 * 8193 / 2 * 256 / 197e12
    sz = weights.sizes_of(r.config)
    assert costs.flash_least_seconds(sz, 8192, 1, r.peaks) == \
        pytest.approx(least)
    reader = _reader("flash_roofline.glm-4.7-flash")
    assert reader.read(r) == pytest.approx(100 * least * 10 / 3.0)
    assert reader.read(r) < 100
    # a program without the scope has no such group: nothing to read
    r.trace = dict(r.trace, ops=[("fusion:kOutput", 2.0)])
    assert reader.read(r) is None
    assert reader.read(_reading(trace=None)) is None


def test_grouped_roofline_follows_the_rows_the_probe_counted():
    r = _reading()
    sz = weights.sizes_of(r.config)
    # at 4096 rows a layer the MXU binds both products; 3 of each
    at = costs.grouped_least_seconds(sz, [4096.0] * 5, r.peaks)
    assert at == pytest.approx(
        5 * 3 * 2 * 4096 * (2048 * 3072 + 1536 * 2048) / 197e12)
    # with no rows the held experts' matrices are still read
    assert costs.grouped_least_seconds(sz, [0.0], r.peaks) == pytest.approx(
        3 * 2 * 8 * (2048 * 3072 + 1536 * 2048) / 819e9)
    reader = _reader("grouped_roofline.glm-4.7-flash")
    assert reader.read(r) == pytest.approx(100 * at * 10 / 0.3)
    r.counters = dict(r.counters, routed_rows_layer_4=3 * 8192)
    assert reader.read(r) > 100 * at * 10 / 0.3
    # the parent's program counts no rows in a fifth layer, or none
    assert reader.read(_reading(counters={})) is None
    assert _reader("mfu.glm-4.7-flash").read(r) == pytest.approx(
        100 * 29.70120880128e12 * 10 / 7.5 / 197e12)
