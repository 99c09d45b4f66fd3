"""The sliding-window sequence tower (grouped-query attention over a
window of keys under plain rotary positions, three to one with full
attention under a YaRN rule, beside softmax-routed gated experts without
a shared expert) against the benchmark's plain reference, at small
widths on the CPU with weights made from a seed.

The reference (``benchmarks/chip/reference_window_seq.py``) imports
nothing of ``persia_tpu``: the rotations and the YaRN frequencies
written out, attention a block of queries at a time against its band or
its causal keys, the experts one at a time under a dense mask, Adam
written out.

Tolerances. Float32 program against the reference at ``highest``
precision: 2e-4 of each array's largest entry, as the other towers'
tests have it (the kernel's blockwise softmax and the experts' sorted
dispatch add in another order than the reference does). The bfloat16
trainer against the float32 reference: the limits of the other towers'
tests, which are bfloat16's at these widths.
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
import costs_window_seq as costs  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import reference_window_seq as ref  # noqa: E402
import weights_window_seq as weights  # noqa: E402
from placements import device_seq_window as placement  # noqa: E402

from persia_tpu import tracing  # noqa: E402
from persia_tpu.models import hybrid_seq  # noqa: E402
from persia_tpu.parallel.device_mode import (  # noqa: E402
    make_device_mode_trainer,
)
from persia_tpu.parallel.mesh import make_mesh  # noqa: E402
from persia_tpu.parallel.train import next_item_cross_entropy  # noqa: E402

F32 = jnp.float32
YARN = {"factor": 16, "original_positions": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
# the cell's pattern at toy widths: a window of 16 keys over histories of
# 48; 16 routed, 4 a token, 4 held
SZ = {"pattern": "WEWEWEAE", "hidden": 64, "vocab": 512, "eps": 1e-6,
      "heads": 4, "kv_heads": 2, "head_dim": 16, "window": 16,
      "rope_theta": 500000, "yarn": YARN,
      "experts_routed": 16, "experts_held": [0, 1, 2, 3],
      "experts_per_token": 4, "expert_width": 32}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8}
CELL = "mellum2-12b-a2.5b.device-histories8k"


def _config():
    path = os.path.join(BENCH_DIR, "configs", "mellum2-12b-a2.5b.json")
    with open(path) as f:
        return json.load(f)


def _highest(f, *args):
    with jax.default_matmul_precision("highest"):
        return f(*args)


def _gap(a, b):
    """Largest entry of ``|a - b|`` over ``b``'s largest."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


def _close(a, b, rtol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = np.abs(b).max() + 1e-30
    np.testing.assert_allclose(a / scale, b / scale, atol=rtol)


def _layer_params(leaves, i):
    return {name.split(".", 1)[1]: v for name, v in leaves.items()
            if name.startswith(f"L{i}.") and not name.endswith(".norm")}


def _mixer(kind, sz=SZ):
    return placement.build_tower(sz, compute_dtype=F32)._mixer(kind, 1.0)


def _tower_params(leaves, sz=SZ):
    """The program's parameter tree out of the benchmark's leaves."""
    tree = {}
    for name, path in placement.leaf_paths(sz).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = jnp.copy(leaves[name])
    return tree


# --- the rotary rules --------------------------------------------------------


def _hugging_face_yarn(dim, base, factor, original, beta_fast, beta_slow,
                       attention_factor=None):
    """``_compute_yarn_parameters`` of Hugging Face's rope utilities,
    written out: (inv_freq, attention_factor)."""
    def get_mscale(scale, mscale=1):
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    if attention_factor is None:
        attention_factor = get_mscale(factor)

    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation_factor = 1 - ramp
    inv_freq = ((1.0 / (factor * pos_freqs)) * (1 - extrapolation_factor)
                + (1.0 / pos_freqs) * extrapolation_factor)
    return inv_freq, attention_factor


def test_the_yarn_rule_is_the_published_keys_read_as_hugging_face_does():
    """Under the config's keys the program's frequencies and amplitude
    are those of the formula written out above (float32 there, as the
    formula computes them), the config's own ``attention_factor`` among
    them, and the softmax scale stays ``128^(-1/2)``; the reference's
    frequencies are the same."""
    sz = weights.sizes_of(_config())
    rule = placement.yarn_rule(sz["yarn"])
    want, factor = _hugging_face_yarn(128, 500000, 16, 8192, 32, 1)
    stated, stated_factor = _hugging_face_yarn(
        128, 500000, 16, 8192, 32, 1, sz["yarn"]["attention_factor"])
    np.testing.assert_array_equal(want, stated)
    got = hybrid_seq.yarn_frequencies(128, 500000, rule)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_frequencies(128, 500000, sz["yarn"]),
                               want, rtol=1e-6)
    amplitude = (hybrid_seq.yarn_mscale(rule.factor, rule.mscale)
                 / hybrid_seq.yarn_mscale(rule.factor, rule.mscale_all_dim))
    assert amplitude == pytest.approx(factor, rel=1e-12)
    assert amplitude == pytest.approx(stated_factor, rel=1e-12)
    assert hybrid_seq.softmax_scale(128, rule) == pytest.approx(
        128 ** -0.5, rel=1e-15)
    # the ramp runs over pairs 18 to 35: the first 18 keep their
    # frequency, the last 28 are divided by 16
    plain = 500000.0 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(got[:18], plain[:18], rtol=1e-12)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-12)


def test_rotary_under_the_rule_scales_cos_and_sin_by_the_amplitude():
    """A vector at position t: each pair turned by t times its
    frequency and its length times the amplitude."""
    rule = placement.yarn_rule(YARN)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 6, 2, 16)), F32)
    got = np.asarray(hybrid_seq.rotary(x, 500000, rule))
    freq = hybrid_seq.yarn_frequencies(16, 500000, rule)
    angle = np.arange(6)[:, None, None] * freq
    a, b = np.asarray(x)[..., :8], np.asarray(x)[..., 8:]
    m = YARN["attention_factor"]
    want = np.concatenate([m * (a * np.cos(angle) - b * np.sin(angle)),
                           m * (b * np.cos(angle) + a * np.sin(angle))], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# --- each mixer, forward and gradients ---------------------------------------


@pytest.mark.parametrize("kind,layer", [("W", 0), ("A", 6)])
@pytest.mark.parametrize("t", [48, 40])
def test_an_attention_mixer_and_its_gradients_match_the_reference(kind, layer,
                                                                  t):
    p = _layer_params(weights.make(11, SZ), layer)
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(2, t, 64)), F32)
    c = jnp.asarray(rng.normal(size=(2, t, 64)), F32)
    mixer = _mixer(kind)
    assert (mixer.window, mixer.rope_scaling is None) == (
        (16, True) if kind == "W" else (None, False))

    def mine(p, u):
        return mixer.apply({"params": p}, u)

    def theirs(p, u):
        return ref.attention(kind, p, u, SZ, lambda v: v)

    _close(_highest(mine, p, u), _highest(theirs, p, u))
    got = _highest(jax.jit(jax.grad(lambda p, u: jnp.sum(c * mine(p, u)),
                                    argnums=(0, 1))), p, u)
    want = _highest(jax.jit(jax.grad(lambda p, u: jnp.sum(c * theirs(p, u)),
                                     argnums=(0, 1))), p, u)
    assert set(got[0]) == set(p) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    for name in p:
        _close(got[0][name], want[0][name])
    _close(got[1], want[1])


def test_a_window_layer_reads_its_window_and_nothing_ahead():
    """Moving an input at position s moves the window layer's output at
    t exactly for s <= t < s + window."""
    p = _layer_params(weights.make(5, SZ), 0)
    mixer = _mixer("W")
    u = np.random.default_rng(2).normal(size=(1, 48, 64)).astype(np.float32)
    base = np.asarray(_highest(lambda: mixer.apply({"params": p},
                                                   jnp.asarray(u))))
    s = 20
    moved = u.copy()
    moved[:, s] += 1.0
    out = np.asarray(_highest(lambda: mixer.apply({"params": p},
                                                  jnp.asarray(moved))))
    changed = np.abs(out - base).max(axis=(0, 2)) > 1e-6
    np.testing.assert_array_equal(
        changed, (np.arange(48) >= s) & (np.arange(48) < s + SZ["window"]))


def test_a_w_layer_needs_a_window():
    tower = placement.build_tower(SZ, attn_window=None)
    with pytest.raises(ValueError, match="attn_window"):
        tower.init(jax.random.key(0), [],
                   [(jnp.ones((1, 8, 64)), jnp.ones((1, 8), bool))])


# --- the share: what one chip of an expert-parallel job computes ------------


def test_the_eight_shares_of_the_router_add_up_to_the_uncut_layer():
    """16 routed, 4 a token, in eight shares of 2, no shared expert: the
    eight shares' parts are the uncut reference layer, and every (token,
    expert) pair is routed to one share."""
    sz = dict(SZ, experts_held=list(range(16)))
    whole = _layer_params(weights.make(21, sz), 1)
    u = jnp.asarray(np.random.default_rng(8).normal(size=(2, 40, 64)), F32)
    want = _highest(lambda: ref.experts(whole, u, sz, lambda v: v,
                                        held=list(range(16))))
    total, rows = 0.0, 0
    for first in range(0, 16, 2):
        ids = [first, first + 1]
        part = dict(whole, w1=whole["w1"][np.asarray(ids)],
                    w2=whole["w2"][np.asarray(ids)])
        mixer = _mixer("E", dict(sz, experts_held=ids))
        out, state = _highest(lambda: mixer.apply(
            {"params": part}, u, mutable=["intermediates"]))
        total = total + out
        rows += int(np.sum(state["intermediates"]["routed_rows"][0]))
    _close(total, want)
    assert rows == 2 * 40 * 4       # every pair, once


# --- the tower through the trainer against the reference, float32 ------------


def _batches(n, histories=2, t=48, seed=0):
    rng = np.random.default_rng(seed)
    seq = rng.integers(0, SZ["vocab"] - 1, size=(n, histories, t + 1))
    return [(s[:, :-1], s[:, 1:]) for s in seq]


def _feed(items, targets):
    rows = reference.row_index(targets, SZ["vocab"], "hashed")
    return ({placement.SLOT: jnp.asarray(items + 1, jnp.int32)},
            jnp.asarray(rows, jnp.int32))


def _trainer(**more):
    model = placement.build_model(SZ, **more)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    sample = {placement.SLOT: jnp.ones((1, 32), jnp.int32)}
    params, opt_state, step = make_device_mode_trainer(
        model, placement.build_optimizer(OPT), mesh, [], sample,
        loss_fn=next_item_cross_entropy)
    return mesh, params, opt_state, step


def _trainer_first_step(leaves, items, targets, **more):
    """The float32 trainer's loss and every leaf's first gradient (from
    Adam's first moment after one step)."""
    mesh, _, opt_state, step = _trainer(compute_dtype=F32, **more)
    ids, label = _feed(items, targets)
    with mesh, jax.default_matmul_precision("highest"):
        _, opt_state, loss = step(_tower_params(leaves), opt_state, [], ids,
                                  label)
    return float(loss), {
        n: np.asarray(placement._get(opt_state[0].mu, p)) / (1 - OPT["b1"])
        for n, p in placement.leaf_paths(SZ).items()}


def _reference_first_step(leaves, items, targets):
    rows = jnp.asarray(reference.row_index(items, SZ["vocab"], "hashed"))
    target = jnp.asarray(reference.row_index(targets, SZ["vocab"], "hashed"))
    qz = lambda v: v  # noqa: E731

    def loss(leaves):
        # the device table hands its rows to the tower in bfloat16, and
        # their cotangent back in bfloat16: so does the reference here
        h = leaves["table"][rows].astype(jnp.bfloat16).astype(F32)
        for i, kind in enumerate(SZ["pattern"]):
            p = {name.split(".", 1)[1]: v for name, v in leaves.items()
                 if name.startswith(f"L{i}.")}
            h = ref.layer(kind, p, h, SZ, qz)
        return ref.head_loss({"final_norm": leaves["final_norm"],
                              "head": leaves["head"]}, h, target, SZ, qz)

    value, grads = _highest(jax.jit(jax.value_and_grad(loss)), leaves)
    return float(value), {n: np.asarray(g) for n, g in grads.items()}


@pytest.fixture(scope="module")
def first_step():
    leaves = weights.make(11, SZ)
    items, targets = _batches(1, seed=4)[0]
    return leaves, items, targets, _reference_first_step(leaves, items,
                                                         targets)


def _worst(first_step, **more):
    """The loss's relative gap and the worst leaf's gradient gap of the
    float32 trainer (``more`` changing its tower) against the reference."""
    leaves, items, targets, (want_loss, want) = first_step
    loss, got = _trainer_first_step(leaves, items, targets, **more)
    assert set(got) == set(want)
    return (abs(loss - want_loss) / abs(want_loss),
            max(_gap(got[n], want[n]) for n in want))


def test_the_float32_tower_through_the_trainer_matches_the_reference(
        first_step):
    """WE WE WE AE: the loss and every leaf's first gradient, the
    table's, the routers' and both kinds of attention's among them,
    within 2e-4 of each array's largest entry. The tower computes in
    float32; the item table hands it bfloat16 rows whatever the tower's
    dtype, and the reference is given the same rows."""
    _, _, _, (_, want) = first_step
    assert all(np.abs(g).max() > 0 for g in want.values())
    loss_gap, grad_gap = _worst(first_step)
    assert loss_gap < 2e-5 and grad_gap < 2e-4, (loss_gap, grad_gap)


@pytest.mark.parametrize("broken", [
    {"attn_window": 4096},      # the window layers made causal
    {"rope_scaling": hybrid_seq.YarnRule(16, 8192, 32, 1, mscale=0.0)},
])                              # the full layers without the amplitude
def test_the_comparison_sees_the_window_and_the_amplitude(first_step,
                                                          broken):
    """The same comparison fails, by far, for a tower whose window layers
    see every earlier key and for one whose full layers leave cos and sin
    at length one: the tolerance above is no room for either."""
    loss_gap, grad_gap = _worst(first_step, **broken)
    assert grad_gap > 50 * 2e-4, (loss_gap, grad_gap)


# --- through the bfloat16 trainer, as on the chip ---------------------------


@pytest.fixture(scope="module")
def built():
    tracing.enable_tracing(True)
    try:
        mesh, params, opt_state, step = _trainer()
    finally:
        tracing.enable_tracing(False)
    return {"mesh": mesh, "step": step, "params": params,
            "opt_state": opt_state, "model": placement.build_model(SZ)}


def test_three_trainer_steps_match_the_reference(built):
    """The loss, the first gradient (from Adam's first moment) and the
    state after three Adam steps, through ``make_device_mode_trainer`` in
    bfloat16, against the float32 reference. The limits are bfloat16's
    at these widths, as the other towers' tests have them."""
    seed, batches = 17, _batches(3)
    leaves, paths = weights.make(seed, SZ), placement.leaf_paths(SZ)
    params = _tower_params(leaves)
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


def test_the_step_carries_its_scopes(built):
    items, targets = _batches(1)[0]
    ids, label = _feed(items, targets)
    with built["mesh"]:
        text = built["step"].lower(built["params"], built["opt_state"], [],
                                   ids, label).as_text(debug_info=True)
    for scope in ("tower", "sliding_attention", "full_attention", "rotary",
                  "window_attention", "flash_attention", "experts",
                  "experts_route", "experts_grouped", "item_head",
                  "optimizer"):
        assert f"{scope}/" in text or f"{scope})" in text, scope
    # each kind's kernel calls innermost in its own scope
    for nested in ("layer_0/sliding_attention/mixer/window_attention",
                   "layer_4/sliding_attention/mixer/rotary",
                   "layer_6/full_attention/mixer/flash_attention",
                   "layer_6/full_attention/mixer/rotary",
                   "layer_7/experts/mixer/experts_grouped"):
        assert nested in text, nested
    for absent in ("layer_0/sliding_attention/mixer/flash_attention",
                   "layer_6/full_attention/mixer/window_attention",
                   "experts_shared"):
        assert absent not in text, absent


def test_routed_rows_counts_the_eight_expert_layers(built):
    ids, _ = _feed(*_batches(1)[0])
    rows = hybrid_seq.routed_rows(built["model"], built["params"], [], ids)
    assert rows.shape == (4, 4)


# the digest of the train step's lowered text at the keye cell's
# rehearsal sizes (interpreted Pallas bodies and all), recorded before
# the flash kernels took a window: its flash calls, which pass no
# window, lower to the program they lowered to before the kernels took
# one. (`tests/test_sparse_seq_tower.py` holds the four other sequence
# towers' digests, which the same commit gives.) A later change that
# alters this program on purpose records its own digest here.
# Recorded anew with `dispatch_pairs`' loops, and with the grouped
# product's transposed call at its own tiling (see
# `tests/test_sparse_seq_tower.py`).
KEYE = {"keye-vl-2.0-30b-a3b.device-histories8k": "f6d98ad9614e829d"}


@pytest.mark.parametrize("cell_name", sorted(KEYE))
def test_the_keye_tower_lowers_to_the_parent_s_program(cell_name):
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    _, cell, config, _ = man.cell(cell_name)
    other = importlib.import_module(f"placements.{cell['placement']}")
    sz = other.weights.sizes_of(config, cell["rehearsal"]["tower"])
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    ids = {other.SLOT: jnp.ones((1, 128), jnp.int32)}
    params, opt_state, step = make_device_mode_trainer(
        other.build_model(sz), other.build_optimizer(config["optimizer"]),
        mesh, [], ids, loss_fn=other.loss_of(sz))
    with mesh:
        text = step.lower(params, opt_state, [], ids,
                          jnp.ones((1, 128), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        KEYE[cell_name]


# --- the configuration, its costs and its readers ----------------------------


def test_the_configuration_states_the_parameters_it_runs():
    config = _config()
    sz = weights.sizes_of(config)
    assert weights.parameters(sz) == config["parameters_as_run"] == 624072960
    assert sz["pattern"] == "WEWEWEAE" * 2 and sz["experts_routed"] == 64
    assert sz["experts_held"] == list(range(8)) and sz["vocab"] == 12288
    assert (sz["window"], sz["heads"], sz["kv_heads"], sz["head_dim"],
            sz["expert_width"], sz["experts_per_token"]) == (
        1024, 32, 4, 128, 896, 8)
    model = placement.build_model(sz)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), [],
                           {placement.SLOT: jnp.ones((1, 16), jnp.int32)}))
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    assert len(leaves) == len(weights.leaf_specs(sz)) == 8 * 9 + 3
    assert sum(int(np.prod(x.shape)) for x in leaves) == 624072960
    # one attention layer, one expert layer
    per = {kind: sum(int(np.prod(s)) for _, s, _ in
                     weights.layer_leaves(kind, sz)) for kind in "WAE"}
    assert per == {"W": 21233664, "A": 21233664,
                   "E": 8 * 3 * 2304 * 896 + 2304 * 64}


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's row stands in the file unchanged but
    the five it lists as reduced, whose published values stand beside
    them; ``rope_parameters`` is copied whole."""
    config = _config()
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "mlp_layer_types": ["sparse"] * 4,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    for key, value in published.items():
        if key in ("layer_types", "mlp_layer_types"):
            # the published period, twice over the eight layers run
            assert config[key] == value * 2, key
        elif key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 8, 12288)
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    entry = next(c for c in man.doc["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "mlp_layer_types",
         "num_experts", "vocab_size"])
    assert entry["source"] == config["source"]
    assert "8 chips share each layer" in config["deployment"]


def test_the_costs_are_counted_from_the_widths():
    """363.2 M forward multiply-accumulates an event at 8192 positions,
    17.85 TFLOP a step; a window layer attends 7 864 832 pairs, 0.234 of
    a full layer's 33 558 528."""
    sz = weights.sizes_of(_config())
    assert costs.band_pairs(8192, 1024) == 7864832
    assert costs.causal_pairs(8192) == 33558528
    assert costs.band_pairs(8192, 1024) / costs.causal_pairs(8192) == \
        pytest.approx(0.234, abs=1e-3)
    assert costs.band_pairs(500, 1024) == costs.causal_pairs(500)
    macs = costs.forward_macs_per_event(sz, 8192)
    assert macs["attention_project"] == pytest.approx(169.87e6, rel=1e-4)
    assert macs["full_attention"] == 2 * 2 * 4096 * 33558528 / 8192
    assert macs["window_attention"] == 6 * 2 * 4096 * 7864832 / 8192
    assert macs["experts_routed"] == 8 * (2304 * 64 + 3 * 2304 * 896)
    assert macs["head"] == 2304 * 12288
    assert sum(macs.values()) == pytest.approx(363.2e6, rel=1e-3)
    assert costs.train_flops_per_event(_config(), 8192) * 8192 == \
        pytest.approx(17.85e12, rel=1e-3)


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
    for i in range(8):
        counters[f"routed_rows_layer_{i}"] = 3 * 8192
    r = types.SimpleNamespace(
        trace={"steps": 10.0, "window_s": 4.0,
               "ops": [("window_attention", 0.3), ("flash_attention", 0.4),
                       ("gmm", 0.2), ("tgmm", 0.1),
                       ("fusion:kOutput", 2.0)]},
        peaks=peaks, config=_config(), batch=8192, chips=1,
        counters=counters,
        env=types.SimpleNamespace(mix={"session_length": 8192}))
    for k, v in more.items():
        setattr(r, k, v)
    return r


def test_the_readers_read_each_kind_of_layer_apart():
    r = _reading()
    sz = weights.sizes_of(r.config)
    # seven products of 32 x 128 a pair: 2.29 ms a window layer over the
    # band, 9.77 ms a full layer over the causal pairs
    window = costs.window_least_seconds(sz, 8192, 1, r.peaks)
    assert window == pytest.approx(6 * 2 * 7 * 4096 * 7864832 / 197e12)
    assert window / 6 == pytest.approx(2.29e-3, rel=5e-3)
    full = costs.flash_least_seconds(sz, 8192, 1, r.peaks)
    assert full == pytest.approx(2 * 2 * 7 * 4096 * 33558528 / 197e12)
    assert full / 2 == pytest.approx(9.77e-3, rel=5e-3)
    reader = _reader("window_roofline.mellum2-12b-a2.5b")
    assert reader.read(r) == pytest.approx(100 * window * 10 / 0.3)
    assert 0 < reader.read(r) < 100
    reader = _reader("flash_roofline.mellum2-12b-a2.5b")
    assert reader.read(r) == pytest.approx(100 * full * 10 / 0.4)
    at = costs.grouped_least_seconds(sz, [8192.0] * 8, r.peaks)
    assert at == pytest.approx(
        8 * 3 * 2 * 8192 * (2304 * 1792 + 896 * 2304) / 197e12)
    grouped = _reader("grouped_roofline.mellum2-12b-a2.5b")
    assert grouped.read(r) == pytest.approx(100 * at * 10 / 0.3)
    assert grouped.read(_reading(counters={})) is None
    assert _reader("mfu.mellum2-12b-a2.5b").read(r) == pytest.approx(
        100 * costs.train_flops_per_event(r.config, 8192) * 8192 * 10 / 4.0
        / 197e12)
    # a step without the band calls (a program whose window layers are no
    # such calls) has nothing to read in the window's group
    r.trace = dict(r.trace, ops=[("flash_attention", 1.0)])
    assert _reader("window_roofline.mellum2-12b-a2.5b").read(r) is None
    for name in ("mfu", "window_roofline", "flash_roofline",
                 "grouped_roofline"):
        assert _reader(f"{name}.mellum2-12b-a2.5b").read(
            _reading(trace=None)) is None


def test_the_cell_is_in_the_manifest_with_its_four_readers():
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    assert man.validate()
    entry, cell, config, _ = man.cell(CELL)
    assert (entry["chips"], entry["traffic"], cell["placement"]) == (
        1, "histories8k", "device_seq_window")
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert {"mfu.mellum2-12b-a2.5b", "window_roofline.mellum2-12b-a2.5b",
            "flash_roofline.mellum2-12b-a2.5b",
            "grouped_roofline.mellum2-12b-a2.5b"} <= names
    assert set(cell["limits_why"]) >= set(cell["limits"])
    assert cell["sizes"] == man.cell(
        "keye-vl-2.0-30b-a3b.device-histories8k")[1]["sizes"]
