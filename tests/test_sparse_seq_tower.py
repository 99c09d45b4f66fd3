"""The selected-attention sequence tower (grouped-query attention over
the keys a learned indexer selects, beside softmax-routed gated experts
without a shared expert) against the benchmark's plain reference, at
small widths on the CPU with weights made from a seed.

The reference (``benchmarks/chip/reference_sparse_seq.py``) imports
nothing of ``persia_tpu``: dense index scores and attention scores a
block of queries at a time, the selection by a stable sort's ranks (the
program finds a threshold by bisection), the experts one at a time under
a dense mask, Adam written out.

Tolerances. Float32 program against the reference at ``highest``
precision, each side selecting for itself: 2e-4 of each array's largest
entry, as the other towers' tests have it (the kernel's blockwise
softmax, the tiles' sums and the experts' sorted dispatch add in another
order than the reference does); the selections themselves are equal
entry for entry. The bfloat16 trainer against the float32 reference,
each side selecting for itself as the chip's comparison runs it: the
limits of the other towers' tests, which are bfloat16's at these widths.
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
import costs_sparse_seq as costs  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import reference_sparse_seq as ref  # noqa: E402
import weights_sparse_seq as weights  # noqa: E402
from placements import device_seq_sparse as placement  # noqa: E402

from persia_tpu import metrics, tracing  # noqa: E402
from persia_tpu.models import hybrid_seq  # noqa: E402
from persia_tpu.parallel.device_mode import (  # noqa: E402
    make_device_mode_trainer,
)
from persia_tpu.parallel.mesh import make_mesh  # noqa: E402
from persia_tpu.parallel.train import (  # noqa: E402
    next_item_cross_entropy,
    next_item_cross_entropy_indexed,
)

F32 = jnp.float32
# the cell's pattern at toy widths: 8 of the causal keys a query, tiles
# of 16 queries; 16 routed, 4 a token, 4 held
SZ = {"pattern": "SESE", "hidden": 64, "vocab": 512, "eps": 1e-6,
      "heads": 4, "kv_heads": 2, "head_dim": 16, "rope_theta": 1e7,
      "index_heads": 4, "index_dim": 16, "index_rope_dim": 8, "topk": 8,
      "index_tile": 16, "index_loss_weight": 1.0,
      "experts_routed": 16, "experts_held": [0, 1, 2, 3],
      "experts_per_token": 4, "expert_width": 32}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8}
CELL = "keye-vl-2.0-30b-a3b.device-histories8k"


def _config():
    path = os.path.join(BENCH_DIR, "configs", "keye-vl-2.0-30b-a3b.json")
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


def test_selected_attention_and_its_gradients_match_the_reference():
    """Output, alignment loss, selection and every gradient of ``sum(c *
    output) + 2 loss``, each side selecting for itself, 40 positions in
    tiles of 8 under a top 8."""
    sz = dict(SZ, index_tile=8)
    p = _layer_params(weights.make(11, sz), 0)
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(2, 40, 64)), F32)
    c = jnp.asarray(rng.normal(size=(2, 40, 64)), F32)
    mixer = _mixer("S", sz)

    def mine(p, u):
        return mixer.apply({"params": p}, u)

    def theirs(p, u):
        return ref.selected_attention(p, u, sz, lambda v: v)

    for got, want in zip(_highest(mine, p, u), _highest(theirs, p, u)):
        _close(got, want)

    def grads(f):
        return _highest(jax.jit(jax.grad(
            lambda p, u: jnp.sum(c * f(p, u)[0]) + 2.0 * f(p, u)[1],
            argnums=(0, 1))), p, u)

    got, want = grads(mine), grads(theirs)
    assert set(got[0]) == set(p)
    for name in p:
        _close(got[0][name], want[0][name])
    _close(got[1], want[1])


@pytest.mark.parametrize("t", [40, 24])
def test_softmax_experts_without_a_shared_one_match_the_reference(t):
    """``SparseExperts(scoring="softmax", shared_width=0)``: no shared
    leaves, and output and gradients of the plain loop over the held
    experts under softmax scores renormalised over the chosen eight."""
    p = _layer_params(weights.make(11, SZ), 1)
    assert set(p) == {"router", "w1", "w2"}
    u = jnp.asarray(np.random.default_rng(3).normal(size=(2, t, 64)), F32)
    c = jnp.asarray(np.random.default_rng(4).normal(size=(2, t, 64)), F32)
    mixer = _mixer("E")
    assert mixer.scoring == "softmax" and mixer.shared_width == 0
    assert mixer.scaling == 1.0

    def mine(p, u):
        return mixer.apply({"params": p}, u)

    def theirs(p, u):
        return ref.experts(p, u, SZ, lambda v: v)

    _close(_highest(mine, p, u), _highest(theirs, p, u))
    got = _highest(jax.jit(jax.grad(lambda p, u: jnp.sum(c * mine(p, u)),
                                    argnums=(0, 1))), p, u)
    want = _highest(jax.jit(jax.grad(lambda p, u: jnp.sum(c * theirs(p, u)),
                                     argnums=(0, 1))), p, u)
    assert set(got[0]) == set(p)
    for name in p:
        _close(got[0][name], want[0][name])
    _close(got[1], want[1])


# the digest of the train step's lowered text at the four accepted
# sequence cells' rehearsal sizes (interpreted Pallas bodies and all),
# recorded on the parent of PR 41 (5bad77c): with their arguments
# (sigmoid scores, a shared expert, no `S`), `SparseExperts`, `_Layer`,
# the flash kernels and the tower lower to the program they lowered to
# before they knew of a softmax router, of a layer without a shared
# expert, of a mask a pair or of a layer with a loss of its own,
# operation for operation. A later change that alters these programs on
# purpose records its own digests here.
ACCEPTED = {
    "nemotron-3-nano-30b-a3b.device-histories8k": "a543cfedb432b0b5",
    "glm-4.7-flash.device-histories8k": "453a82043b12ce2c",
    "xing4.0-29b-a4b.device-histories8k": "34eb17747ebd05f8",
    "kimi-linear-48b-a3b.device-histories8k": "cc826348bd01b75d",
}


@pytest.mark.parametrize("cell_name", sorted(ACCEPTED))
def test_the_accepted_towers_keep_their_trees_and_their_programs(cell_name):
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    _, cell, config, _ = man.cell(cell_name)
    other = importlib.import_module(f"placements.{cell['placement']}")
    sz = other.weights.sizes_of(config, cell["rehearsal"]["tower"])
    model = other.build_model(sz)
    tags = model.tower.step_tags()
    assert tags["expert_scoring"] == "sigmoid"
    assert (tags["selected_layers"], tags["index_fused_layers"],
            tags["select_topk"], tags["index_heads"]) == (0, 0, 0, 0)
    loss = (other.loss_of(sz) if hasattr(other, "loss_of")
            else next_item_cross_entropy)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    ids = {other.SLOT: jnp.ones((1, 128), jnp.int32)}
    params, opt_state, step = make_device_mode_trainer(
        model, other.build_optimizer(config["optimizer"]), mesh, [], ids,
        loss_fn=loss)
    # the parameter tree is the configuration's, leaf for leaf: every
    # expert layer still has its shared expert
    paths = other.leaf_paths(sz)
    assert len(jax.tree_util.tree_leaves(params)) == len(paths)
    for name, shape, _ in other.weights.leaf_specs(sz):
        assert other._get(params, paths[name]).shape == tuple(shape), name
    assert any(name.endswith("shared_w1") for name in paths)
    with mesh:
        text = step.lower(params, opt_state, [], ids,
                          jnp.ones((1, 128), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        ACCEPTED[cell_name]


# --- the share: what one chip of an expert-parallel job computes ------------


def test_the_four_shares_of_the_router_add_up_to_the_uncut_layer():
    """16 routed, 4 a token, in four shares of 4, no shared expert: the
    four shares' parts are the uncut reference layer, and every (token,
    expert) pair is routed to one share."""
    sz = dict(SZ, experts_held=list(range(16)))
    whole = _layer_params(weights.make(21, sz), 1)
    u = jnp.asarray(np.random.default_rng(8).normal(size=(2, 40, 64)), F32)
    want = _highest(lambda: ref.experts(whole, u, sz, lambda v: v,
                                        held=list(range(16))))
    total, rows = 0.0, 0
    for first in range(0, 16, 4):
        ids = list(range(first, first + 4))
        part = dict(whole, w1=whole["w1"][np.asarray(ids)],
                    w2=whole["w2"][np.asarray(ids)])
        mixer = _mixer("E", dict(sz, experts_held=ids))
        out, state = _highest(lambda: mixer.apply(
            {"params": part}, u, mutable=["intermediates"]))
        total = total + out
        rows += int(np.sum(state["intermediates"]["routed_rows"][0]))
    _close(total, want)
    assert rows == 2 * 40 * 4       # every pair, once


# --- the tower against the reference, float32 --------------------------------


def _reference_loss(leaves, rows, target, sz=SZ):
    """(cross entropy, summed alignment loss), selecting for itself."""
    qz = lambda v: v  # noqa: E731
    h, index_loss = leaves["table"][rows], 0.0
    for i, kind in enumerate(sz["pattern"]):
        p = {name.split(".", 1)[1]: v for name, v in leaves.items()
             if name.startswith(f"L{i}.")}
        h = ref.layer(kind, p, h, sz, qz)
        if kind == "S":
            h, part = h
            index_loss = index_loss + part
    return ref.head_loss({"final_norm": leaves["final_norm"],
                          "head": leaves["head"]}, h, target, sz, qz), \
        index_loss


def test_the_float32_tower_and_its_gradients_match_the_reference():
    """Cross entropy, alignment loss and every leaf's gradient of their
    weighted sum through SE SE, 40 positions in tiles of 16, the
    indexer's leaves among them."""
    leaves = weights.make(11, SZ)
    rng = np.random.default_rng(3)
    seq = rng.integers(1, SZ["vocab"], size=(2, 41))
    rows, target = jnp.asarray(seq[:, :-1]), jnp.asarray(seq[:, 1:])
    tower = placement.build_tower(SZ, compute_dtype=F32)
    paths = placement.leaf_paths(SZ)

    def mine(leaves):
        logits, index_loss = tower.apply(
            {"params": _tower_params(leaves)["tower"]}, [],
            [(leaves["table"][rows], jnp.ones(rows.shape, bool))])
        return (next_item_cross_entropy_indexed((logits, index_loss),
                                                target, 0.5),
                (next_item_cross_entropy(logits, target), index_loss))

    def theirs(leaves):
        ce, index_loss = _reference_loss(leaves, rows, target)
        return ce + 0.5 * index_loss, (ce, index_loss)

    (_, got_parts), got = _highest(
        jax.jit(jax.value_and_grad(mine, has_aux=True)), leaves)
    (_, want_parts), want = _highest(
        jax.jit(jax.value_and_grad(theirs, has_aux=True)), leaves)
    for a, b in zip(got_parts, want_parts):
        assert float(a) == pytest.approx(float(b), rel=2e-5)
    assert float(want_parts[1]) > 1e-3      # two layers' alignment loss
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


def _probe(model):
    """Jitted, as the placement's: op by op the CPU rounds bfloat16
    where a compiled fusion does not, and a score at the cut falls the
    other way."""
    return jax.jit(lambda params, ids: hybrid_seq.selected_keys(
        model, params, [], ids))


def test_three_trainer_steps_match_the_reference(built):
    """The loss (alignment loss included), the first gradient (from
    Adam's first moment) and the state after three Adam steps, through
    ``make_device_mode_trainer`` in bfloat16, against the float32
    reference, each side selecting for itself as on the chip. The limits
    are bfloat16's at these widths (8 bits of mantissa through four
    sublayers), as the other towers' tests have them."""
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
    assert all(x > 1e-3 for x in theirs["index_losses"])


def test_the_probe_reads_the_selection_the_step_makes(built):
    """``selected_keys`` (a forward pass outside the step) and the
    selection inside the differentiated, rematerialised training pass
    are the same entry for entry, so the placement's counters count the
    pairs the steps attend."""
    model, params = built["model"], built["params"]
    ids, label = _feed(*_batches(1, seed=5)[0])
    probed = np.asarray(_probe(model)(params, ids))
    assert probed.shape == (2, 2, 48, 48) and probed.dtype == np.int8

    def loss(params):
        out, sown = model.apply({"params": params}, [], ids, train=True,
                                mutable=["selections"])
        return placement.loss_of(SZ)(out, label), sown["selections"]

    (_, sown), _ = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    stepped = np.stack([
        np.asarray(sown["tower"][f"layer_{i}"]["mixer"]["selected"][0])
        for i in (0, 2)])
    np.testing.assert_array_equal(probed, stepped)
    count = np.minimum(np.arange(48) + 1, SZ["topk"])
    np.testing.assert_array_equal(probed.sum(-1),
                                  np.broadcast_to(count, (2, 2, 48)))
    pairs = np.asarray(jax.jit(lambda params, ids: hybrid_seq.selected_pairs(
        model, params, [], ids))(params, ids))
    np.testing.assert_array_equal(pairs, [2 * count.sum()] * 2)
    assert costs.selected_pairs(48, SZ["topk"]) == count.sum()


def test_the_build_is_tagged_and_the_step_carries_its_scopes(built):
    tags = built["span"].tags
    assert tags["tower_layers"] == "SESE"
    assert (tags["selected_layers"], tags["select_topk"],
            tags["index_heads"]) == (2, 8, 4)
    # the indexer's tile of 16 admits the kernels' block of queries (a
    # tile of 8 admits none: the plain form whatever the history)
    assert tags["index_fused_layers"] == 2
    assert placement.build_tower(dict(SZ, index_tile=8)).step_tags()[
        "index_fused_layers"] == 0
    assert tags["expert_scoring"] == "softmax"
    assert tags["attention_positions"] == 1
    assert tags["key_width"] == 16 and tags["value_width"] == 16
    assert tags["attention_residuals_kept"] == 2     # the two `S` layers
    assert tags["experts_routed"] == 16 and tags["expert_matrices"] == 3
    gauges = metrics.default_registry()
    for name, value in (("selected_layers", 2), ("select_topk", 8),
                        ("index_fused_layers", 2), ("index_heads", 4), ("attention_positions", 1),
                        ("tower_layers", 4), ("experts_held", 4),
                        ("key_width", 16), ("value_width", 16)):
        assert gauges.gauge(f"device_mode_{name}").value == value
    # a word is a gauge of 1 under its label, not a gauge of its length
    assert gauges.gauge("device_mode_expert_scoring",
                        labels={"choice": "softmax"}).value == 1
    ids, label = _feed(*_batches(1)[0])
    with built["mesh"]:
        text = built["step"].lower(built["params"], built["opt_state"], [],
                                   ids, label).as_text(debug_info=True)
    for scope in ("tower", "selected_attention", "select_project", "rotary",
                  "index_scores", "index_select", "flash_attention",
                  "index_target", "select_out", "experts", "experts_route",
                  "experts_grouped", "item_head", "optimizer"):
        assert f"{scope}/" in text or f"{scope})" in text, scope
    # the kernels' calls innermost in the layer's scope (the indexer's
    # scopes lie inside the tiles' loops, whose bodies the lowered text
    # locates apart from the layer that holds them)
    for nested in ("layer_0/selected_attention/mixer/flash_attention",
                   "layer_2/selected_attention/mixer/select_project",
                   "layer_2/selected_attention/mixer/select_out",
                   "layer_3/experts/mixer/experts_grouped"):
        assert nested in text, nested
    assert "experts_shared" not in text


def test_the_probes_count_two_layers_of_each_kind(built):
    ids, _ = _feed(*_batches(1)[0])
    rows = hybrid_seq.routed_rows(built["model"], built["params"], [], ids)
    assert rows.shape == (2, 4)
    both = hybrid_seq.sown(built["model"], built["params"], [], ids,
                           "intermediates", "selections")
    assert both[0].shape == (2, 4) and both[1].shape == (2, 2, 48, 48)


def test_a_tower_with_selected_attention_takes_one_stream_and_no_module():
    for more in ({"residual_streams": 4}, {"mtp_depth": 1}):
        tower = placement.build_tower(SZ, **more)
        with pytest.raises(ValueError, match="selected attention"):
            tower.init(jax.random.key(0), [],
                       [(jnp.ones((1, 8, 64)), jnp.ones((1, 8), bool))])


# --- the configuration, its costs and its readers ----------------------------


def test_the_configuration_states_the_parameters_it_runs():
    config = _config()
    sz = weights.sizes_of(config)
    assert weights.parameters(sz) == config["parameters_as_run"] == 659190016
    assert sz["pattern"] == "SE" * 6 and sz["experts_routed"] == 128
    assert sz["experts_held"] == list(range(16)) and sz["vocab"] == 18992
    assert (sz["topk"], sz["index_heads"], sz["index_dim"],
            sz["index_tile"]) == (2048, 16, 64, 512)
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size"}
    tower = placement.build_tower(sz)
    assert tower.step_tags() == {
        "tower_layers": "SESESESESESE", "experts_held": tuple(range(16)),
        "experts_routed": 128, "expert_matrices": 3, "mtp_depth": 0,
        "residual_streams": 1, "sinkhorn_iters": 0, "key_width": 128,
        "value_width": 128, "attention_residuals_kept": 6,
        "hyper_fused_sublayers": 0, "kda_layers": 0, "kda_fused_layers": 0,
        "kda_heads": 0, "kda_chunk": 0, "attention_positions": 1,
        "selected_layers": 6, "index_fused_layers": 6, "select_topk": 2048,
        "index_heads": 16,
        "expert_scoring": "softmax"}
    model = placement.build_model(sz)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), [],
                           {placement.SLOT: jnp.ones((1, 16), jnp.int32)}))
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    assert len(leaves) == len(weights.leaf_specs(sz)) == 99
    assert sum(int(np.prod(x.shape)) for x in leaves) == 659190016
    # one selected-attention layer and its indexer, one expert layer, as
    # ISSUE 41 counts them
    per = {kind: sum(int(np.prod(s)) for _, s, _ in
                     weights.layer_leaves(kind, sz)) for kind in "SE"}
    assert per == {"S": 21135744, "E": 75497472 + 262144}
    indexer = sum(int(np.prod(s)) for n, s, _ in
                  weights.layer_leaves("S", sz) if n in weights.INDEXER)
    assert indexer == 2261120


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's row stands in the file unchanged but
    the three it lists as reduced, whose published values stand beside
    them; ``sa_config`` and ``rope_scaling`` are copied whole."""
    config = _config()
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 18992)
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    entry = next(c for c in man.doc["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert "8 chips share each layer" in config["deployment"]


def test_the_costs_are_counted_from_the_widths():
    """308.8 M forward multiply-accumulates an event at 8192 positions,
    15.18 TFLOP a step, attention over the 14.68 M selected pairs a
    layer of the 33.56 M causal ones (ISSUE 41's count)."""
    sz = weights.sizes_of(_config())
    assert costs.selected_pairs(8192, 2048) == 14681088
    assert costs.selected_pairs(1000, 2048) == 1000 * 1001 // 2
    macs = costs.forward_macs_per_event(sz, 8192)
    total = sum(macs.values())
    assert total == pytest.approx(308.8e6, rel=1e-3)
    assert macs["select_project"] == 6 * (18874624 - 256)
    assert macs["index_project"] == 6 * (2261120 - 128)
    assert macs["index_scores"] == 6 * 16 * 64 * 8193 / 2
    assert macs["selected_attention"] == 6 * 2 * 4096 * 14681088 / 8192
    assert macs["experts_routed"] == 6 * (262144 + 1.0 * 4718592)
    assert macs["head"] == 2048 * 18992
    chosen = (macs["index_project"] + macs["index_scores"]
              + macs["selected_attention"])
    assert chosen / total == pytest.approx(0.41, abs=0.01)
    assert costs.train_flops_per_event(_config(), 8192) * 8192 == \
        pytest.approx(15.18e12, rel=1e-3)


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
    counters = {"routed_batches": 3, "selected_batches": 3}
    for i in range(6):
        counters[f"routed_rows_layer_{i}"] = 3 * 8192
        counters[f"selected_pairs_layer_{i}"] = 3 * 14681088
    r = types.SimpleNamespace(
        trace={"steps": 10.0, "window_s": 9.0,
               "ops": [("flash_attention", 1.5), ("gmm", 0.2),
                       ("tgmm", 0.1), ("fusion:kOutput", 5.0)]},
        peaks=peaks, config=_config(), batch=8192, chips=1,
        counters=counters,
        env=types.SimpleNamespace(mix={"session_length": 8192}))
    for k, v in more.items():
        setattr(r, k, v)
    return r


def test_the_readers_read_six_layers_of_each_kind():
    r = _reading()
    sz = weights.sizes_of(r.config)
    # seven products of 32 x 128 a selected pair, once: 4.27 ms a layer
    least = 6 * 2 * 7 * 32 * 128 * 14681088 / 197e12
    assert costs.selected_least_seconds(sz, 8192, [14681088.0] * 6,
                                        r.peaks) == pytest.approx(least)
    assert least == pytest.approx(25.6e-3, rel=5e-3)
    flash = _reader("flash_roofline.keye-vl-2.0-30b-a3b")
    assert flash.read(r) == pytest.approx(100 * least * 10 / 1.5)
    assert flash.read(r) < 100
    assert flash.read(_reading(counters={})) is None
    r.trace = dict(r.trace, ops=[("fusion:kOutput", 2.0)])
    assert flash.read(r) is None and flash.read(_reading(trace=None)) is None
    r = _reading()
    at = costs.grouped_least_seconds(sz, [8192.0] * 6, r.peaks)
    by_ops = 6 * 3 * 2 * 8192 * (2048 * 1536 + 768 * 2048) / 197e12
    assert at == pytest.approx(by_ops)
    grouped = _reader("grouped_roofline.keye-vl-2.0-30b-a3b")
    assert grouped.read(r) == pytest.approx(100 * at * 10 / 0.3)
    assert grouped.read(_reading(counters={})) is None
    assert _reader("mfu.keye-vl-2.0-30b-a3b").read(r) == pytest.approx(
        100 * costs.train_flops_per_event(r.config, 8192) * 8192 * 10 / 9.0
        / 197e12)
    # the index scores' least time: three products of 16 x 64 a causal
    # pair, 1.05 ms a layer
    index_least = costs.index_least_seconds(sz, 8192, 1, r.peaks)
    assert index_least == pytest.approx(
        6 * 2 * 3 * 1024 * 8192 * 8193 / 2 / 197e12)
    # over the kernels' group, and the same under a fusion's kind (the
    # pullback's call, wrapped in a fusion named after it); a step whose
    # index scores are no kernel (the parent's) has nothing to read
    index = _reader("index_roofline.keye-vl-2.0-30b-a3b")
    assert index.read(r) is None
    r.trace = dict(r.trace, ops=r.trace["ops"] + [
        ("index_scores", 0.1), ("index_scores:kCustom", 0.15),
        ("index_scores_not", 9.0)])
    assert index.read(r) == pytest.approx(100 * index_least * 10 / 0.25)
    assert index.read(r) < 100 and index.read(_reading(trace=None)) is None


def test_the_cell_is_in_the_manifest_with_its_four_readers():
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    assert man.validate()
    entry, cell, config, _ = man.cell(CELL)
    assert (entry["chips"], entry["traffic"], cell["placement"]) == (
        1, "histories8k", "device_seq_sparse")
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    # the index scores are kernels with a trace group of their own
    assert {"mfu.keye-vl-2.0-30b-a3b", "flash_roofline.keye-vl-2.0-30b-a3b",
            "grouped_roofline.keye-vl-2.0-30b-a3b",
            "index_roofline.keye-vl-2.0-30b-a3b"} <= names
    assert [m["workloads"] for m in man.metrics_of(CELL, "per_layer")
            if m["name"].startswith("index_roofline")] == [[CELL]]
    assert set(cell["limits_why"]) >= set(cell["limits"])
    assert cell["sizes"] == man.cell(
        "kimi-linear-48b-a3b.device-histories8k")[1]["sizes"]
