"""The delta-rule sequence tower (Kimi Delta Attention layers beside
latent attention with one direct query projection and without positions,
a gated dense feed-forward, gated sparse experts beside a shared expert)
against the benchmark's plain reference, at small widths on the CPU with
weights made from a seed.

The reference (``benchmarks/chip/reference_kda_seq.py``) imports nothing
of ``persia_tpu``: the delta rule stepped position by position (the
program runs it in chunks), attention as the full score matrix, the
experts one at a time under a dense mask, Adam written out.

Tolerances. Float32 program against the reference at ``highest``
precision: 2e-4 of each array's largest entry, as the other towers'
tests have it (the chunked recurrence, the kernel's blockwise softmax
and the experts' sorted dispatch add in another order than the
reference does). The bfloat16 trainer against the float32 reference:
the limits of the other towers' tests, which are bfloat16's at these
widths.
"""

import hashlib
import importlib
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
import costs_kda_seq as costs  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import reference_kda_seq as ref  # noqa: E402
import weights_kda_seq as weights  # noqa: E402
from reference_latent_seq import shared_expert  # noqa: E402
from placements import device_seq_kda as placement  # noqa: E402

from persia_tpu import metrics, tracing  # noqa: E402
from persia_tpu.models import hybrid_seq  # noqa: E402
from persia_tpu.parallel.device_mode import (  # noqa: E402
    make_device_mode_trainer,
)
from persia_tpu.parallel.mesh import make_mesh  # noqa: E402
from persia_tpu.parallel.train import next_item_cross_entropy  # noqa: E402

F32 = jnp.float32
# the cell's pattern at toy widths: four K to one L; 16 routed, 4 a
# token, 4 held; a chunk of 32 (two blocks) so that 40 positions pad
SZ = {"pattern": "KDKEKELEKE", "hidden": 64, "vocab": 512, "eps": 1e-5,
      "kda_heads": 2, "kda_head_dim": 16, "conv_kernel": 4,
      "kda_chunk": 32, "kda_l2_eps": 1e-6,
      "dt_limits": [1e-3, 1e-1, 1e-4], "heads": 4, "q_rank": None,
      "kv_rank": 16, "nope_dim": 16, "rope_dim": 8, "v_dim": 16,
      "positions": False, "dense_width": 96, "experts_routed": 16,
      "experts_held": [0, 1, 2, 3], "experts_per_token": 4,
      "expert_width": 32, "shared_width": 32, "routed_scaling": 2.446}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8}
CELL = "kimi-linear-48b-a3b.device-histories8k"


def _config():
    path = os.path.join(BENCH_DIR, "configs", "kimi-linear-48b-a3b.json")
    with open(path) as f:
        return json.load(f)


def _highest(f, *args):
    with jax.default_matmul_precision("highest"):
        return f(*args)


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


# --- each mixer, forward and gradients ---------------------------------------


@pytest.mark.parametrize("kind,t", [("K", 40), ("K", 64), ("L", 40),
                                    ("D", 24), ("E", 40)],
                         ids=["kda_padded", "kda_whole_chunks",
                              "latent_attention", "dense_ffn", "experts"])
def test_a_mixer_and_its_gradients_match_the_reference(kind, t):
    i = SZ["pattern"].index(kind)
    p = _layer_params(weights.make(11, SZ), i)
    u = jnp.asarray(np.random.default_rng(3).normal(size=(2, t, 64)), F32)
    w = jnp.asarray(np.random.default_rng(4).normal(size=(2, t, 64)), F32)
    mixer = _mixer(kind)

    def mine(p, u):
        return mixer.apply({"params": p}, u)

    def theirs(p, u):
        return ref.MIXERS[kind](p, u, SZ, lambda v: v)

    _close(_highest(mine, p, u), _highest(theirs, p, u))
    got = _highest(jax.jit(jax.grad(lambda p, u: jnp.sum(w * mine(p, u)),
                                    argnums=(0, 1))), p, u)
    want = _highest(jax.jit(jax.grad(lambda p, u: jnp.sum(w * theirs(p, u)),
                                     argnums=(0, 1))), p, u)
    assert set(got[0]) == set(p)
    for name in p:
        _close(got[0][name], want[0][name])
    _close(got[1], want[1])


def test_the_delta_rule_reads_order_and_nothing_ahead():
    """Causal (a later change reaches no earlier output), and the layer
    carries position: the same inputs in another order give another
    last output, through the convolutions and the decaying state."""
    p = _layer_params(weights.make(5, SZ), 0)
    mixer = _mixer("K")
    u = jnp.asarray(np.random.default_rng(1).normal(size=(1, 48, 64)), F32)
    out = _highest(lambda: mixer.apply({"params": p}, u))
    later = u.at[:, 20:].set(0.5)
    out2 = _highest(lambda: mixer.apply({"params": p}, later))
    np.testing.assert_allclose(out[:, :20], out2[:, :20], atol=1e-6)
    assert np.abs(np.asarray(out[:, 20:] - out2[:, 20:])).max() > 1e-3
    swapped = u.at[:, 3].set(u[:, 11]).at[:, 11].set(u[:, 3])
    out3 = _highest(lambda: mixer.apply({"params": p}, swapped))
    assert np.abs(np.asarray(out[:, -1] - out3[:, -1])).max() > 1e-4


def test_latent_attention_without_positions_is_plain_softmax():
    """One direct query projection, nothing rotated: against softmax(q
    k^T / sqrt(24)) v written out with numpy, causal, and blind to the
    order of what it has seen (two earlier positions swapped leave the
    last output as it was, where a rotary key would move it)."""
    p = _layer_params(weights.make(5, SZ), 6)
    assert set(p) == {"q_proj", "kv_a", "kv_norm", "kv_b", "o_proj"}
    mixer = _mixer("L")
    assert mixer.q_rank is None and not mixer.positions
    u = np.random.default_rng(1).normal(size=(1, 32, 64)).astype(np.float32)
    out = np.asarray(_highest(lambda: mixer.apply({"params": p},
                                                  jnp.asarray(u))))
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = u[0].astype(np.float64)
    q = (x @ w["q_proj"]).reshape(32, 4, 24)
    kva = x @ w["kv_a"]
    c = kva[:, :16]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-5) * w["kv_norm"]
    kv = (c @ w["kv_b"]).reshape(32, 4, 32)
    k = np.concatenate([kv[..., :16],
                        np.broadcast_to(kva[:, None, 16:], (32, 4, 8))], -1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(24)
    s = np.where(np.tril(np.ones((32, 32), bool)), s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a = a / a.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", a, kv[..., 16:]).reshape(32, 64) \
        @ w["o_proj"]
    _close(out[0], want, 1e-5)
    swapped = jnp.asarray(u).at[:, 3].set(u[:, 11]).at[:, 11].set(u[:, 3])
    out2 = _highest(lambda: mixer.apply({"params": p}, swapped))
    np.testing.assert_allclose(out[:, -1], out2[:, -1], atol=1e-6)
    # ... and with positions the same swap does move it
    turning = placement.build_tower(
        dict(SZ, positions=True), compute_dtype=F32)._mixer("L", 1.0)
    moved = [_highest(lambda v=v: turning.apply({"params": p}, v))[:, -1]
             for v in (jnp.asarray(u), swapped)]
    assert np.abs(np.asarray(moved[0] - moved[1])).max() > 1e-4


# the digest of the train step's lowered text at the accepted
# latent-attention cells' rehearsal sizes (interpreted Pallas bodies and
# all), recorded on the parent of PR 39 (64fa983): with a low-rank query
# and positions, `LatentAttention` lowers to the program it lowered to
# before it knew of a direct query projection or of attention without
# positions, operation for operation. (`tests/test_hyper_seq_tower.py`
# holds the one-stream towers' digests; the glm cell's is the same
# there.) A later change that alters these programs on purpose records
# its own digests here.
# Recorded anew with `dispatch_pairs`' loops, and with the grouped
# product's transposed call at its own tiling (see
# `tests/test_sparse_seq_tower.py`).
LATENT = {
    "glm-4.7-flash.device-histories8k": "f1a1e3a8b0f46475",
    "xing4.0-29b-a4b.device-histories8k": "da2ea466a746a8f4",
}


@pytest.mark.parametrize("cell_name", sorted(LATENT))
def test_the_accepted_latent_towers_lower_to_the_parent_s_program(cell_name):
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    _, cell, config, _ = man.cell(cell_name)
    other = importlib.import_module(f"placements.{cell['placement']}")
    sz = other.weights.sizes_of(config, cell["rehearsal"]["tower"])
    model = other.build_model(sz)
    assert model.tower.latent_positions and model.tower.latent_q_rank
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
        LATENT[cell_name]


# --- the share: what one chip of an expert-parallel job computes ------------


def test_the_four_shares_of_the_router_add_up_to_the_uncut_layer():
    """16 routed, 4 a token, in four shares of 4: the four shares'
    routed parts, with the shared expert counted once, are the uncut
    reference layer, and every (token, expert) pair is routed to one
    share."""
    sz = dict(SZ, experts_held=list(range(16)))
    whole = _layer_params(weights.make(21, sz), 3)
    u = jnp.asarray(np.random.default_rng(8).normal(size=(2, 40, 64)), F32)
    want = _highest(lambda: ref.experts(whole, u, sz, lambda v: v,
                                        held=list(range(16))))
    shared = _highest(lambda: shared_expert(
        whole, u.reshape(-1, 64), lambda v: v)).reshape(u.shape)
    total, rows = 0.0, 0
    for first in range(0, 16, 4):
        ids = list(range(first, first + 4))
        part = dict(whole, w1=whole["w1"][np.asarray(ids)],
                    w2=whole["w2"][np.asarray(ids)])
        mixer = _mixer("E", dict(sz, experts_held=ids))
        out, state = _highest(lambda: mixer.apply(
            {"params": part}, u, mutable=["intermediates"]))
        total = total + (out - shared)
        rows += int(np.sum(state["intermediates"]["routed_rows"][0]))
    _close(total + shared, want)
    assert rows == 2 * 40 * 4       # every pair, once


# --- the tower against the reference, float32 --------------------------------


def _reference_loss(leaves, rows, target, sz=SZ):
    qz = lambda v: v  # noqa: E731
    h = leaves["table"][rows]
    for i, kind in enumerate(sz["pattern"]):
        p = {name.split(".", 1)[1]: v for name, v in leaves.items()
             if name.startswith(f"L{i}.")}
        h = ref.layer(kind, p, h, sz, qz)
    return ref.head_loss({"final_norm": leaves["final_norm"],
                          "head": leaves["head"]}, h, target, sz, qz)


def test_the_float32_tower_and_its_gradients_match_the_reference():
    """Loss and every leaf's gradient through KD KE KE LE KE, 40
    positions under a chunk of 32."""
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

    got_loss, got = _highest(jax.jit(jax.value_and_grad(mine)), leaves)
    want_loss, want = _highest(jax.jit(jax.value_and_grad(
        lambda leaves: _reference_loss(leaves, rows, target))), leaves)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-5)
    assert set(got) == set(paths)
    for name in paths:
        _close(got[name], want[name])
        assert float(jnp.linalg.norm(want[name])) > 0, name


# --- through the trainer -----------------------------------------------------


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
    state after three Adam steps, through ``make_device_mode_trainer``
    in bfloat16, against the float32 reference. The limits are
    bfloat16's at these widths (8 bits of mantissa through ten
    sublayers), as the other towers' tests have them; a float32 program
    reads a hundred times lower (the test above)."""
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


def test_the_step_carries_its_scopes(built):
    assert built["span"].tags["dense_update_tables"] == 1   # Adam: dense
    assert metrics.default_registry().gauge(
        "device_mode_dense_update_tables").value == 1
    ids, label = _feed(*_batches(1)[0])
    with built["mesh"]:
        text = built["step"].lower(built["params"], built["opt_state"], [],
                                   ids, label).as_text(debug_info=True)
    for scope in ("tower", "kda_attention", "kda_project", "kda_conv",
                  "kda_gates", "kda_scan", "kda_out", "latent_attention",
                  "latent_project", "flash_attention", "dense_ffn",
                  "experts", "experts_route", "experts_grouped",
                  "experts_shared", "item_head", "optimizer"):
        assert f"{scope}/" in text or f"{scope})" in text, scope
    # the op innermost in the layer's scope; nothing rotates
    for nested in ("layer_0/kda_attention/mixer/kda_scan",
                   "layer_8/kda_attention/mixer/kda_gates",
                   "layer_6/latent_attention/mixer/flash_attention",
                   "layer_3/experts/mixer/experts_grouped"):
        assert nested in text, nested
    assert "rotary" not in text


def test_routed_rows_counts_the_four_expert_layers(built):
    ids, _ = _feed(*_batches(1)[0])
    rows = hybrid_seq.routed_rows(built["model"], built["params"], [], ids)
    assert rows.shape == (4, 4)


# --- the configuration, its costs and its readers ----------------------------


def test_the_configuration_states_the_parameters_it_runs():
    config = _config()
    sz = weights.sizes_of(config)
    assert weights.parameters(sz) == config["parameters_as_run"] == 602433408
    assert sz["pattern"] == "KDKEKELEKE" and sz["experts_routed"] == 256
    assert sz["experts_held"] == list(range(8)) and sz["vocab"] == 20480
    assert sz["q_rank"] is None and sz["positions"] is False
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size"}
    model = placement.build_model(sz)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), [],
                           {placement.SLOT: jnp.ones((1, 16), jnp.int32)}))
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    assert len(leaves) == len(weights.leaf_specs(sz))
    assert sum(int(np.prod(x.shape)) for x in leaves) == 602433408
    # one Kimi Delta Attention layer and the latent layer, as ISSUE 39
    # counts them
    per = {kind: sum(int(np.prod(s)) for _, s, _ in
                     weights.layer_leaves(kind, sz)) for kind in "KL"}
    assert per == {"K": 39514272, "L": 29114880}


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's row stands in the file unchanged but
    the three it lists as reduced, whose published values stand beside
    them; ``linear_attn_config`` is copied whole, its layer lists as
    published (the pattern reads the entries up to the depth run)."""
    config = _config()
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 20480)
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    entry = next(c for c in man.doc["configs"]
                 if c["name"] == "kimi-linear-48b-a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert "32 chips share each layer" in config["deployment"]


def test_the_costs_are_counted_from_the_widths():
    """384.0 M forward multiply-accumulates an event at 8192 positions,
    18.88 TFLOP a step; ISSUE 39 reckons 389 M with a recurrence of 11.7
    M where the position-by-position form needs 6.3 M."""
    sz = weights.sizes_of(_config())
    macs = costs.forward_macs_per_event(sz, 8192)
    total = sum(macs.values())
    assert total == pytest.approx(384.03e6, rel=1e-4)
    assert macs["kda_project"] == 4 * (39514272 - 4096 - 32 - 128)
    assert macs["kda_recurrence"] == 4 * 3 * 32 * 128 * 128
    assert macs["latent_project"] == 29114880 - 512
    assert macs["latent_attention"] == 32 * (192 + 128) * 8193 / 2
    assert macs["dense_ffn"] == 63700992
    assert macs["experts_shared"] == 4 * 7077888
    assert macs["experts_routed"] == 4 * (589824 + 0.25 * 7077888)
    assert macs["head"] == 2304 * 20480
    mixers = (macs["kda_project"] + macs["kda_recurrence"]
              + macs["latent_project"] + macs["latent_attention"])
    assert mixers / total == pytest.approx(0.613, abs=2e-3)
    assert costs.train_flops_per_event(_config(), 8192) * 8192 == \
        pytest.approx(18.88e12, rel=1e-3)


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
    counters.update({f"routed_rows_layer_{i}": 3 * 2048 for i in range(4)})
    r = types.SimpleNamespace(
        trace={"steps": 10.0, "window_s": 5.0,
               "ops": [("flash_attention", 0.3), ("gmm", 0.2),
                       ("tgmm", 0.1), ("fusion:kOutput", 2.0)]},
        peaks=peaks, config=_config(), batch=8192, chips=1,
        counters=counters,
        env=types.SimpleNamespace(mix={"session_length": 8192}))
    for k, v in more.items():
        setattr(r, k, v)
    return r


def test_the_readers_read_one_attention_layer_and_four_expert_layers():
    r = _reading()
    sz = weights.sizes_of(r.config)
    # seven products, four at 192 and three at 128: 1152 a unit, once
    least = 2 * 32 * 8192 * 8193 / 2 * 1152 / 197e12
    assert costs.flash_least_seconds(sz, 8192, 1, r.peaks) == \
        pytest.approx(least)
    assert least == pytest.approx(12.56e-3, rel=1e-3)
    flash = _reader("flash_roofline.kimi-linear-48b-a3b")
    assert flash.read(r) == pytest.approx(100 * least * 10 / 0.3)
    assert flash.read(r) < 100
    r.trace = dict(r.trace, ops=[("fusion:kOutput", 2.0)])
    assert flash.read(r) is None and flash.read(_reading(trace=None)) is None
    r = _reading()
    # at 2048 rows a layer the matrices' bytes set the need, not the rows
    at = costs.grouped_least_seconds(sz, [2048.0] * 4, r.peaks)
    by_bytes = 4 * 3 * 2 * (2048 * (2304 + 2048 + 1024 + 2304)
                            + 8 * 3 * 2304 * 1024) / 819e9
    assert at == pytest.approx(by_bytes)
    grouped = _reader("grouped_roofline.kimi-linear-48b-a3b")
    assert grouped.read(r) == pytest.approx(100 * at * 10 / 0.3)
    assert grouped.read(_reading(counters={})) is None
    assert _reader("mfu.kimi-linear-48b-a3b").read(r) == pytest.approx(
        100 * costs.train_flops_per_event(r.config, 8192) * 8192 * 10 / 5.0
        / 197e12)
    # the recurrence's least time: its bytes, q k v o in bfloat16 and g
    # in float32 a channel, beta a head, each and its gradient once
    nbytes = 2 * 8192 * 32 * (128 * 12 + 4)
    assert costs.kda_least_seconds(sz, 8192, 1, r.peaks) == \
        pytest.approx(4 * nbytes / 819e9)


def test_the_cell_is_in_the_manifest_with_its_four_readers():
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    assert man.validate()
    entry, cell, config, _ = man.cell(CELL)
    assert (entry["chips"], entry["traffic"], cell["placement"]) == (
        1, "histories8k", "device_seq_kda")
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    # the recurrence is kernels with a trace group of their own
    assert {"mfu.kimi-linear-48b-a3b", "flash_roofline.kimi-linear-48b-a3b",
            "grouped_roofline.kimi-linear-48b-a3b",
            "kda_roofline.kimi-linear-48b-a3b"} <= names
    assert set(cell["limits_why"]) >= set(cell["limits"])
