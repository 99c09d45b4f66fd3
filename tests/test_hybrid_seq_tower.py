"""The hybrid sequence tower (state-space mixers, sparse experts beside a
shared expert, grouped-query attention) against the benchmark's plain
reference, at small widths on the CPU with weights made from a seed.

The reference (``benchmarks/chip/reference_hybrid_seq.py``) imports
nothing of ``persia_tpu``: the recurrence position by position, the
experts as a loop under a dense mask, attention as the full score
matrix, Adam written out.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
for p in (BENCH_DIR, os.path.join(BENCH_DIR, "placements")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
import device_seq  # noqa: E402
import reference  # noqa: E402
import reference_hybrid_seq as ref  # noqa: E402
import reference_latent_seq as ref_gated  # noqa: E402
import weights_hybrid_seq as weights  # noqa: E402
import weights_latent_seq as weights_gated  # noqa: E402

from persia_tpu import metrics, tracing  # noqa: E402
from persia_tpu.models import hybrid_seq  # noqa: E402
from persia_tpu.ops.ssm_scan import ssm_scan  # noqa: E402
from persia_tpu.parallel.device_mode import (  # noqa: E402
    make_device_mode_trainer,
)
from persia_tpu.parallel.mesh import make_mesh  # noqa: E402
from persia_tpu.parallel.train import next_item_cross_entropy  # noqa: E402

F32 = jnp.float32
SZ = {"pattern": "ME*E", "hidden": 64, "vocab": 512, "eps": 1e-5,
      "ssm_heads": 4, "ssm_head_dim": 16, "ssm_groups": 2, "ssm_state": 16,
      "conv_kernel": 4, "chunk": 32, "experts_routed": 16,
      "experts_held": [0, 1, 2, 3], "experts_per_token": 3,
      "expert_width": 32, "shared_width": 64, "routed_scaling": 2.5,
      "attn_heads": 4, "attn_kv_heads": 2, "attn_head_dim": 16,
      "dt_limits": [1e-3, 1e-1, 1e-4]}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8}


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


# --- the scan ---------------------------------------------------------------


@pytest.mark.parametrize("t", [64, 50, 7], ids=["chunks", "ragged", "short"])
def test_chunked_scan_is_the_sequential_recurrence(t):
    """Forward and every gradient, at a length that is a multiple of the
    chunk, one that is not, and one under a chunk."""
    rng = np.random.default_rng(t)
    bs, heads, p, groups, n = 2, 4, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(bs, t, heads, p)), F32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, size=(bs, t, heads)), F32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, size=(heads,)), F32)
    b = jnp.asarray(rng.normal(size=(bs, t, groups, n)), F32)
    c = jnp.asarray(rng.normal(size=(bs, t, groups, n)), F32)
    w = jnp.asarray(rng.normal(size=(bs, t, heads, p)), F32)

    def chunked(*args):
        return jnp.sum(w * ssm_scan(*args, chunk=32, compute_dtype=F32))

    def stepwise(*args):
        return jnp.sum(w * ref.recurrence(*args))

    args = (x, dt, a, b, c)
    _close(_highest(lambda: ssm_scan(*args, chunk=32, compute_dtype=F32)),
           _highest(lambda: ref.recurrence(*args)))
    got = _highest(jax.grad(chunked, argnums=range(5)), *args)
    want = _highest(jax.grad(stepwise, argnums=range(5)), *args)
    for g, r in zip(got, want):
        _close(g, r)


# --- each mixer, forward and gradients --------------------------------------


def _mixer(kind, held=SZ["experts_held"], activation="relu2"):
    """One float32 mixer of the tower, holding the experts ``held``."""
    return device_seq.build_tower(
        dict(SZ, experts_held=list(held), expert_activation=activation),
        compute_dtype=F32)._mixer(kind, 1.0)


# the expert layer's two forms, each with the reference that has it
ACTIVATIONS = pytest.mark.parametrize("activation", ["relu2", "swiglu"])
REF_EXPERTS = {"relu2": ref, "swiglu": ref_gated}


@pytest.mark.parametrize("kind,t", [("M", 48), ("E", 40), ("*", 40)],
                         ids=["ssm", "experts", "attention"])
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


def test_routing_weights_are_normalised_and_scaled():
    scores = jax.nn.sigmoid(jnp.asarray(
        np.random.default_rng(0).normal(size=(200, 16)), F32))
    chosen, weight = hybrid_seq.route(scores, 3, 2.5)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.5, rtol=1e-6)
    top = np.argsort(-np.asarray(scores), axis=-1)[:, :3]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(top, -1)).all()
    # no ties among seeded scores: the choice is the reference's
    assert len(np.unique(np.asarray(scores))) == scores.size
    u = jnp.asarray(np.random.default_rng(1).normal(size=(200, 64)), F32)
    router = _leaves(5)["L1.router"]
    theirs, their_w = _highest(ref.routing, u, router, SZ)
    mine, my_w = _highest(lambda: hybrid_seq.route(
        jax.nn.sigmoid(u @ router), 3, 2.5))
    assert (np.asarray(mine) == np.asarray(theirs)).all()
    _close(my_w, their_w, 1e-6)


# --- the share: what one chip of an expert-parallel job computes ------------


def _expert_leaves(seed, ids, activation="relu2"):
    """The layer's leaves with the experts ``ids`` of 16 made whole; the
    gated form's first matrices are ``[gate | up]``, twice as wide."""
    sz = dict(SZ, experts_held=list(range(16)))
    if activation == "relu2":
        p = _layer_params(weights.make(seed, sz), 1, sz)
    else:
        key = weights.seed_key(seed)
        p = {n: weights.gen_leaf(key, i, shape, kind, sz) for i, (
            n, shape, kind) in enumerate(weights_gated.layer_leaves("E", sz))}
    return dict(p, w1=p["w1"][np.asarray(ids)], w2=p["w2"][np.asarray(ids)])


@ACTIVATIONS
def test_the_shares_add_up_to_the_uncut_layer(activation):
    """16 routed experts in 4 shares of 4: the four shares' routed parts,
    with the shared expert counted once, are the uncut reference layer."""
    u = jnp.asarray(np.random.default_rng(8).normal(size=(2, 40, 64)), F32)
    whole = _expert_leaves(21, range(16), activation)
    theirs = REF_EXPERTS[activation]
    want = _highest(lambda: theirs.experts(whole, u, SZ, lambda v: v,
                                           held=list(range(16))))
    shared = _highest(lambda: theirs.shared_expert(
        whole, u.reshape(-1, 64), lambda v: v)).reshape(u.shape)
    total, rows = 0.0, 0
    for first in range(0, 16, 4):
        ids = list(range(first, first + 4))
        out, state = _highest(lambda: _mixer("E", ids, activation).apply(
            {"params": _expert_leaves(21, ids, activation)}, u,
            mutable=["intermediates"]))
        total = total + (out - shared)
        rows += int(np.sum(state["intermediates"]["routed_rows"][0]))
    _close(total + shared, want)
    assert rows == 2 * 40 * SZ["experts_per_token"]     # every pair, once


@pytest.mark.parametrize("tokens,per_token,held,routed,rows", [
    (8192, 6, 8, 128, 8192),        # the cell: one pair a token binds
    (256, 3, 4, 16, 384),           # the rehearsal: twice the expected 192
    (80, 3, 16, 16, 240),           # every expert held: every pair
    (80, 3, 5, 16, 150),            # no whole number of them in the 240
], ids=["cell", "rehearsal", "all_held", "uneven"])
def test_pair_buffer_rows_follow_the_share_of_experts_held(
        tokens, per_token, held, routed, rows):
    assert hybrid_seq.pair_buffer_rows(tokens, per_token, held,
                                       routed) == rows


# what each class of token chooses, of 16 experts with 0..3 held: three,
# two, one and none of its pairs are routed here
CHOICES = ((0, 1, 2), (3, 0, 8), (1, 8, 9), (8, 9, 10))


def _steered(p, counts, seed=9):
    """The layer's leaves and an input on which ``counts[c]`` tokens of
    80 choose ``CHOICES[c]``: the token's class is written into its first
    four features, and the router reads them far above the rest."""
    rng = np.random.default_rng(seed)
    router = np.asarray(p["router"]) * 0.01
    router[:4] = 0.0
    for c, chosen in enumerate(CHOICES):
        router[c, list(chosen)] = 1.0
    u = rng.normal(size=(80, 64)).astype(np.float32)
    u[:, :4] = 4.0 * np.eye(4, dtype=np.float32)[
        rng.permutation(np.repeat(np.arange(4), counts))]
    return dict(p, router=jnp.asarray(router)), jnp.asarray(
        u.reshape(2, 40, 64))


def _every_pair_held(p):
    """Scores of the four held experts far above the rest for every
    token, and short of where the sigmoid's gradient underflows."""
    router = np.asarray(p["router"]) * 0.01
    router[:, :4] += np.linspace(0.02, 0.05, 4, dtype=np.float32)
    u = jnp.abs(jnp.asarray(
        np.random.default_rng(9).normal(size=(2, 40, 64)), F32)) + 0.5
    return dict(p, router=jnp.asarray(router)), u


@ACTIVATIONS
@pytest.mark.parametrize("case,held,pairs,passes", [
    ("balanced", 4, None, 1), ("exactly_the_buffer", 4, 120, 1),
    ("one_pair_over", 4, 121, 2), ("every_pair_held", 4, 240, 2),
    ("uneven_buffers", 5, 240, 2)])
def test_no_pair_is_dropped_however_many_buffers_it_takes(case, held, pairs,
                                                          passes, activation):
    """80 tokens, 4 of 16 experts held, top 3: the pair buffer has 120
    rows. Output and every gradient are the reference's whether the held
    pairs fit one buffer or take a second pass of the loop (121 pairs:
    one pair in it; 240: both full), and with 5 held, where the 240 pairs
    are no whole number of buffers of 150; with square-relu experts of
    two matrices and with silu-gated experts of three, through the one
    ``dispatch_pairs`` and its hand-written backward."""
    cap = hybrid_seq.pair_buffer_rows(80, SZ["experts_per_token"], held, 16)
    assert cap == {4: 120, 5: 150}[held]
    ids = list(range(held))
    p = _expert_leaves(33, ids, activation)
    if case == "balanced":
        u = jnp.asarray(np.random.default_rng(9).normal(size=(2, 40, 64)),
                        F32)
    elif pairs == 240:
        p, u = _every_pair_held(p)
    else:       # 90 + 20 + (10 or 11) pairs here
        ones = pairs - 110
        p, u = _steered(p, [30, 10, ones, 40 - ones])
    w = jnp.asarray(np.random.default_rng(4).normal(size=(2, 40, 64)), F32)
    mixer = _mixer("E", ids, activation)

    def theirs(p, u):
        return REF_EXPERTS[activation].experts(p, u, SZ, lambda v: v,
                                               held=ids)

    out, state = _highest(lambda: mixer.apply(
        {"params": p}, u, mutable=["intermediates"]))
    rows = np.asarray(state["intermediates"]["routed_rows"][0])
    if pairs is not None:
        assert rows.sum() == pairs
    if pairs == 240:
        assert rows.max() == 2 * 40         # one expert sees every token
    assert -(-rows.sum() // cap) == passes
    _close(out, _highest(theirs, p, u))
    got = _highest(jax.grad(lambda p, u: jnp.sum(w * mixer.apply(
        {"params": p}, u)), argnums=(0, 1)), p, u)
    want = _highest(jax.grad(lambda p, u: jnp.sum(w * theirs(p, u)),
                             argnums=(0, 1)), p, u)
    assert set(p) == {"router", "w1", "w2", "shared_w1", "shared_w2"}
    for name in p:
        _close(got[0][name], want[0][name])
    _close(got[1], want[1])


# --- the tower through the device-mode trainer ------------------------------


def _batches(n, histories=2, t=48, seed=0):
    rng = np.random.default_rng(seed)
    seq = rng.integers(0, SZ["vocab"] - 1, size=(n, histories, t + 1))
    return [(s[:, :-1], s[:, 1:]) for s in seq]


@pytest.fixture(scope="module")
def built():
    """The tower through ``DeviceModeModel`` and
    ``make_device_mode_trainer``, built once, with the build's span."""
    model = device_seq.build_model(SZ)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    sample = {device_seq.SLOT: jnp.ones((1, SZ["chunk"]), jnp.int32)}
    tracing.enable_tracing(True)
    try:
        params, opt_state, step = make_device_mode_trainer(
            model, device_seq.build_optimizer(OPT), mesh, [], sample,
            loss_fn=next_item_cross_entropy)
        span = [s for s in tracing.default_collector().recent()
                if s.name == "trainer/build_device_step"][-1]
    finally:
        tracing.enable_tracing(False)
    return {"model": model, "mesh": mesh, "step": step, "span": span,
            "params": params, "opt_state": opt_state}


def _params_from(leaves):
    """The program's parameter tree holding copies of ``leaves``."""
    tree = {}
    for name, path in device_seq.leaf_paths(SZ).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = jnp.copy(leaves[name])
    return tree


def _feed(items, targets):
    rows = reference.row_index(targets, SZ["vocab"], "hashed")
    return ({device_seq.SLOT: jnp.asarray(items + 1, jnp.int32)},
            jnp.asarray(rows, jnp.int32))


def test_three_trainer_steps_match_the_reference(built):
    """Losses, the first gradient (from Adam's first moment) and the
    change after three steps, through ``make_device_mode_trainer`` in
    bfloat16, against the float32 reference."""
    seed, batches = 17, _batches(3)
    leaves, paths = _leaves(seed), device_seq.leaf_paths(SZ)
    params = _params_from(leaves)
    shape_of = lambda tree: jax.tree_util.tree_map(jnp.shape, tree)  # noqa: E731
    assert shape_of(params) == shape_of(built["params"])
    # the step donates its state: a copy, so the fixture keeps its own
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
                    n: float(jnp.linalg.norm(device_seq._get(
                        opt_state[0].mu, p))) / (1 - OPT["b1"])
                    for n, p in paths.items()}
    prog["change_norm"] = {
        n: float(jnp.linalg.norm(device_seq._get(params, p) - leaves[n]))
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
    # a state left unchanged reads 1
    still = ref.first_steps(SZ, OPT, lambda: _leaves(seed), rows,
                            fault="unchanged")
    assert check.compare(still, theirs)[0]["change_gap"] == 1.0


def test_the_build_is_tagged_and_the_step_carries_its_scopes(built):
    tags = built["span"].tags
    assert tags["tower_layers"] == "ME*E"
    assert tags["experts_held"] == (0, 1, 2, 3)
    assert tags["experts_routed"] == 16
    assert tags["dense_update_tables"] == 1      # Adam: the dense step
    assert tags["attention_residuals_kept"] == 1     # the one `*` layer
    gauges = metrics.default_registry()
    for name, value in (("tower_layers", 4), ("experts_held", 4),
                        ("experts_routed", 16), ("row_update_tables", 0),
                        ("dense_update_tables", 1),
                        ("attention_residuals_kept", 1)):
        assert gauges.gauge(f"device_mode_{name}").value == value
    ids, label = _feed(*_batches(1)[0])
    with built["mesh"]:
        text = built["step"].lower(built["params"], built["opt_state"], [],
                                   ids, label).as_text(debug_info=True)
    for scope in ("tables_gather", "tower", "ssm_mixer", "ssm_scan",
                  "experts", "experts_route", "experts_grouped",
                  "experts_shared", "attention", "item_head", "optimizer"):
        assert f"{scope}/" in text or f"{scope})" in text, scope
    # nested as PERF.md has them: the mixer's scope holds its parts
    for nested in ("ssm_mixer/mixer/ssm_scan", "experts/mixer/experts_route",
                   "attention/mixer/flash_attention"):
        assert nested in text, nested


def test_routed_rows_names_each_held_expert_of_each_expert_layer(built):
    ids, _ = _feed(*_batches(1)[0])
    rows = np.asarray(jax.jit(lambda p, i: hybrid_seq.routed_rows(
        built["model"], p, [], i))(_params_from(_leaves(3)), ids))
    assert rows.shape == (2, 4) and rows.sum() > 0
    assert rows.sum(axis=1).max() <= 2 * 48 * SZ["experts_per_token"]


def test_a_sequence_slot_returns_its_rows_unpooled():
    from persia_tpu.parallel.device_embedding import DeviceEmbeddingCollection

    slots = DeviceEmbeddingCollection(slot_specs=[("item", 32, 8)],
                                      pooling="none")
    ids = {"item": jnp.asarray([[3, 5, 0, 0], [7, 7, 9, 0]], jnp.int32)}
    variables = slots.init(jax.random.key(0), ids)
    (seq, mask), = slots.apply(variables, ids)
    assert seq.shape == (2, 4, 8) and seq.dtype == jnp.bfloat16
    assert (np.asarray(mask) == (np.asarray(ids["item"]) > 0)).all()
    assert not np.asarray(seq, np.float32)[~np.asarray(mask)].any()
    table = variables["params"]["bag_item"]["table"].unbox()
    np.testing.assert_array_equal(
        np.asarray(seq[1, 0], np.float32),
        np.asarray(table[(7 % 31) + 1].astype(jnp.bfloat16), np.float32))


def test_the_configuration_states_the_parameters_it_runs():
    """The benchmark's configuration of this tower: the cut it lists and
    the parameter count it states are what its sizes come to."""
    import json

    with open(os.path.join(BENCH_DIR, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    sz = weights.sizes_of(config)
    assert weights.parameters(sz) == config["parameters_as_run"] == 666962944
    assert sz["pattern"] == "MEMEM*EME" and sz["experts_routed"] == 128
    assert sz["experts_held"] == list(range(8)) and sz["vocab"] == 16384
    assert set(config["reduced"]) == {"num_hidden_layers",
                                      "n_routed_experts", "vocab_size"}
    tower = device_seq.build_tower(sz)
    assert tower.step_tags() == {"tower_layers": "MEMEM*EME",
                                 "experts_held": tuple(range(8)),
                                 "experts_routed": 128, "expert_matrices": 2,
                                 "mtp_depth": 0, "residual_streams": 1,
                                 "sinkhorn_iters": 0, "key_width": 128,
                                 "value_width": 128,
                                 "attention_residuals_kept": 1,
                                 "hyper_fused_sublayers": 0,
                                 "kda_layers": 0, "kda_fused_layers": 0,
                                 "kda_heads": 0, "kda_chunk": 0,
                                 "attention_positions": 0,
                                 "selected_layers": 0,
                                 "index_fused_layers": 0, "select_topk": 0,
                                 "index_heads": 0, "expert_scoring": "sigmoid"}


def test_kernels_roofline_is_the_algorithm_s_need_at_the_rows_routed():
    """The reader's least time counts each pass once (no recomputed
    call), follows the rows the placement counted as routed, and finds
    nothing to read where no rows were counted."""
    import importlib.util
    import json
    import types

    import costs_hybrid_seq as costs

    with open(os.path.join(BENCH_DIR, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    sz = weights.sizes_of(config)
    # 7 products of 32 x 8192 x 8193 / 2 x 128 MACs over 197 TFLOP/s
    flash = costs.flash_least_seconds(sz, 8192, 1, peaks)
    assert flash == pytest.approx(7 * 2 * 32 * 8192 * 8193 / 2 * 128
                                  / 197e12)
    # at the expected 3072 rows a layer the MXU binds; with no rows the
    # held experts' matrices are still read: 6 products a layer
    at = costs.grouped_least_seconds(sz, [3072.0] * 4, peaks)
    assert at == pytest.approx(4 * 6 * 2 * 3072 * 2688 * 1856 / 197e12)
    assert costs.grouped_least_seconds(sz, [0.0], peaks) == pytest.approx(
        6 * 2 * 8 * 2688 * 1856 / 819e9)
    assert costs.grouped_least_seconds(sz, [6144.0] * 4, peaks) > 1.9 * at

    spec = importlib.util.spec_from_file_location(
        "kernels_roofline", os.path.join(BENCH_DIR, "layer_metrics",
                                         "kernels_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    counters = {"routed_batches": 3}
    counters.update({f"routed_rows_layer_{i}": 3 * 3072 for i in range(4)})
    r = types.SimpleNamespace(
        trace={"steps": 10.0, "ops": [("flash_attention", 0.4),
                                      ("gmm", 0.1), ("tgmm", 0.05),
                                      ("fusion:kOutput", 2.0)]},
        peaks=peaks, config=config, batch=8192, counters=counters,
        env=types.SimpleNamespace(mix={"session_length": 8192}))
    assert reader.read(r) == pytest.approx(
        100 * (flash + at) * 10 / 0.55)
    r.counters = {}
    assert reader.read(r) is None


def test_next_item_cross_entropy_leaves_out_positions_without_a_target():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(2, 5, 11)),
                         F32)
    target = jnp.asarray([[1, 2, 3, -1, -1], [4, 5, 6, 7, -1]], jnp.int32)
    logp = np.asarray(jax.nn.log_softmax(logits))
    want = -np.mean([logp[b, t, target[b, t]] for b in range(2)
                     for t in range(5) if target[b, t] >= 0])
    np.testing.assert_allclose(
        float(next_item_cross_entropy(logits, target)), want, rtol=1e-6)
    assert float(next_item_cross_entropy(
        logits, jnp.full((2, 5), -1, jnp.int32))) == 0.0
