"""Workload zoo: generator determinism, zipf fidelity, worker-tier
ragged pooling parity, multi-task gradient accounting, scenario
registry round-trips, the planner's predicted-vs-measured delta, and
every scenario trained end to end through the hybrid stack (falling
loss, held-out AUC floor, the ragged-free wire pin)."""

import numpy as np
import pytest

from persia_tpu import hotness as hot
from persia_tpu.config import EmbeddingSchema, SlotConfig
from persia_tpu.worker import middleware as mw
from persia_tpu.workloads import generator as gen
from persia_tpu.workloads import get_scenario, scenario_names


# --- generator determinism ----------------------------------------------

@pytest.mark.parametrize("name", ["dlrm", "seqrec", "multitask"])
def test_generator_determinism_same_seed_identical_batches(name):
    sc = get_scenario(name, smoke=True)
    a = [b.to_bytes() for b in sc.batches(3 * 64, 64, seed=7)]
    b = [b.to_bytes() for b in sc.batches(3 * 64, 64, seed=7)]
    assert a == b
    c = [b.to_bytes() for b in sc.batches(3 * 64, 64, seed=8)]
    assert a != c


def test_hidden_task_is_seed_independent():
    """Different seeds are disjoint draws from the SAME task: the
    hidden per-sign weights must not move with the generator seed."""
    ids = np.arange(1, 200, dtype=np.uint64)
    w1 = gen.hidden_weight(np.full(len(ids), 3, np.uint64), ids)
    w2 = gen.hidden_weight(np.full(len(ids), 3, np.uint64), ids)
    np.testing.assert_array_equal(w1, w2)
    assert abs(float(w1.mean())) < 0.3  # ~N(0,1), not degenerate
    assert 0.5 < float(w1.std()) < 1.5


# --- zipf fidelity -------------------------------------------------------

@pytest.mark.parametrize("alpha", [0.9, 1.05, 1.3])
def test_generated_traffic_fits_configured_alpha(alpha):
    """The skew knob is real: exact rank counts of a generated stream
    fit back (hotness.fit_zipf_alpha) to the configured alpha."""
    rng = np.random.default_rng(3)
    vocab = 5000
    cdf = gen.zipf_cdf(vocab, alpha)
    ranks = gen.zipf_ranks(rng, cdf, 400_000)
    counts = np.bincount(ranks, minlength=vocab)
    counts = np.sort(counts[counts > 0])[::-1].astype(float)
    fitted = hot.fit_zipf_alpha(counts[:1000])
    assert fitted is not None
    assert abs(fitted - alpha) < 0.15, (fitted, alpha)


def test_dlrm_traffic_alpha_through_armed_holder():
    """End-to-end telemetry fit: ONE dlrm table's generated sign stream
    through a hotness-armed holder fits back near the configured alpha
    — the planner's input is trustworthy on traffic it did not
    generate. (PS hotness tables are keyed by dim; feeding a single
    feature keeps the stream un-blended — a full 26-table run merges
    disjoint zipf heads per dim, which legitimately flattens the
    blended fit.)"""
    from persia_tpu.ps.store import EmbeddingHolder

    spec = gen.CriteoSpec.build(scale=0.2, alpha=1.1)
    h = EmbeddingHolder(500_000, 4, hotness=True)
    h.configure("bounded_uniform", {"lower": -0.01, "upper": 0.01})
    h.register_optimizer({
        "type": "adagrad", "lr": 0.05, "initialization": 0.01,
        "g_square_momentum": 1.0, "vectorwise_shared": False})
    # the widest-vocab table has the most fit-able head
    t = int(np.argmax(spec.vocabs))
    feature = gen.CRITEO_SLOT_NAMES[t]
    dim = spec.dims[t]
    for b in gen.dlrm_batches(40 * 1024, 1024, spec=spec,
                              requires_grad=False):
        f = next(x for x in b.id_type_features if x.name == feature)
        h.lookup(f.signs, dim, training=True)
    snap = h.hotness_snapshot()
    assert snap.get("enabled")
    fit = hot.summary_view(snap)["tables"][str(dim)]["zipf_alpha"]
    assert fit is not None
    assert abs(fit - 1.1) < 0.35, fit


# --- ragged pooling parity ----------------------------------------------

def _ragged_feature(rng, n=7, vocab=60, max_len=9):
    from persia_tpu.data.batch import IDTypeFeature

    rows = [rng.integers(1, vocab,
                         size=rng.integers(1, max_len),
                         dtype=np.uint64) for _ in range(n)]
    return IDTypeFeature("s", rows), rows


@pytest.mark.parametrize("pooling", ["sum", "mean", "last3"])
def test_pooled_worker_result_bitmatches_dense_reference(pooling):
    """The pooled (batch, dim) worker output is BIT-identical to a
    per-sample dense loop that sums rows in CSR (arrival) order and
    applies the same post-scale — the contract the backend-parity and
    reproducibility goldens extend to the new pooling modes."""
    rng = np.random.default_rng(11)
    feat, rows = _ragged_feature(rng)
    df = mw.dedup_feature(feat)
    dim = 6
    emb = rng.normal(size=(df.num_distinct, dim)).astype(np.float32)
    slot = SlotConfig("s", dim, pooling=pooling)
    out = mw.postprocess_feature(df, slot, emb).embeddings

    row_of = {int(s): i for i, s in enumerate(df.distinct_signs)}
    ref = np.zeros((len(rows), dim), np.float32)
    for i, r in enumerate(rows):
        sel = r[-3:] if pooling == "last3" else r
        acc = np.zeros(dim, np.float32)
        for sid in sel:  # element order == CSR order
            acc = acc + emb[row_of[int(sid)]]
        if pooling == "mean":
            acc = acc * (np.float32(1.0) / np.float32(len(r)))
        ref[i] = acc
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("pooling", ["sum", "mean", "last3"])
def test_pooled_gradient_is_adjoint_of_forward(pooling):
    """The pooled forward is a linear map F; aggregate_gradients must
    be its adjoint: <F(E), G> == <E, aggregate(G)> for random E, G."""
    rng = np.random.default_rng(5)
    feat, rows = _ragged_feature(rng)
    df = mw.dedup_feature(feat)
    dim = 4
    slot = SlotConfig("s", dim, pooling=pooling)
    E = rng.normal(size=(df.num_distinct, dim)).astype(np.float32)
    G = rng.normal(size=(len(rows), dim)).astype(np.float32)

    lhs = float((mw.postprocess_feature(df, slot, E).embeddings
                 * G).sum())
    agg = mw.aggregate_gradients(df, slot, G)
    assert agg.shape == (df.num_distinct, dim)
    rhs = float((E * agg).sum())
    np.testing.assert_allclose(lhs, rhs, rtol=1e-4, atol=1e-5)


def test_pooling_config_validation():
    with pytest.raises(ValueError):
        SlotConfig("x", 4, pooling="bogus")
    with pytest.raises(ValueError):
        SlotConfig("x", 4, pooling="mean", embedding_summation=False)
    with pytest.raises(ValueError):
        SlotConfig("x", 4, pooling="last2", sqrt_scaling=True)
    from persia_tpu.config import HashStackConfig

    with pytest.raises(ValueError):
        SlotConfig("x", 4, pooling="mean",
                   hash_stack_config=HashStackConfig(2, 100))
    assert SlotConfig("x", 4, pooling="last10").pooling_last_n == 10


def test_pooling_survives_yaml_roundtrip():
    """Schema -> service yaml dict -> EmbeddingSchema keeps pooling
    (the worker subprocess must pool exactly like the in-process
    worker)."""
    from persia_tpu.service.helper import _schema_to_yaml_dict

    sc = get_scenario("seqrec", smoke=True)
    raw = _schema_to_yaml_dict(sc.schema)
    back = EmbeddingSchema.from_dict(raw)
    for name, slot in sc.schema.slots_config.items():
        assert back.get_slot(name).pooling == slot.pooling


def test_pooled_lookup_through_worker_and_service_wire():
    """A pooled slot round-trips the worker lookup AND the service
    serialization as a plain SumEmbedding — no new wire kind."""
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service.serialization import (
        pack_lookup_result,
        unpack_lookup_result,
    )
    from persia_tpu.worker.middleware import SumEmbedding
    from persia_tpu.worker.worker import EmbeddingWorker

    sc = get_scenario("seqrec", smoke=True)
    h = EmbeddingHolder(100_000, 2)
    h.configure("bounded_uniform", {"lower": -0.05, "upper": 0.05})
    h.register_optimizer({
        "type": "adagrad", "lr": 0.05, "initialization": 0.01,
        "g_square_momentum": 1.0, "vectorwise_shared": False})
    worker = EmbeddingWorker(sc.schema, [h])
    try:
        b = next(iter(sc.batches(32, 32, requires_grad=False)))
        out = worker.lookup_direct(b.id_type_features, training=True)
    finally:
        worker.close()
    for name in (gen.SEQ_HISTORY_SLOT, gen.SEQ_CLICKS_SLOT):
        assert isinstance(out[name], SumEmbedding)
        assert out[name].embeddings.shape == (32, 16)
    back = unpack_lookup_result(pack_lookup_result(out))
    for name, r in out.items():
        assert isinstance(back[name], SumEmbedding)
        np.testing.assert_array_equal(back[name].embeddings,
                                      r.embeddings)


# --- multi-task shared-table gradient accounting -------------------------

def test_multitask_shared_table_gradient_accounting():
    """With L = L_click + L_convert over ONE shared embedding input,
    the per-sign gradient the worker aggregates equals the SUM of the
    two tasks' per-sign gradients — no double count, no lost half."""
    import jax
    import jax.numpy as jnp

    from persia_tpu.workloads.models import MultiTaskDNN

    sc = get_scenario("multitask", smoke=True)
    batch = next(iter(sc.batches(16, 16)))
    model = MultiTaskDNN(num_tasks=2)
    non_id = [jnp.asarray(batch.non_id_type_features[0].data)]
    rng = np.random.default_rng(0)
    emb_inputs = [
        jnp.asarray(rng.normal(size=(16, sc.schema.get_slot(f.name).dim))
                    .astype(np.float32))
        for f in batch.id_type_features
    ]
    params = model.init(jax.random.key(0), non_id, emb_inputs)
    label = jnp.asarray(batch.labels[0].data)

    def task_loss(embs, t):
        pred = model.apply(params, non_id, embs)
        p = jnp.clip(pred[:, t], 1e-7, 1 - 1e-7)
        y = label[:, t]
        return -jnp.mean(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))

    def joint(embs):
        return task_loss(embs, 0) + task_loss(embs, 1)

    g_joint = jax.grad(joint)(emb_inputs)
    g_click = jax.grad(lambda e: task_loss(e, 0))(emb_inputs)
    g_conv = jax.grad(lambda e: task_loss(e, 1))(emb_inputs)
    for gj, gc, gv in zip(g_joint, g_click, g_conv):
        np.testing.assert_allclose(np.asarray(gj),
                                   np.asarray(gc) + np.asarray(gv),
                                   rtol=1e-4, atol=1e-5)
    # and through the worker's aggregation: per-sign accounting is the
    # same linear sum (duplicate signs accumulate both tasks' shares)
    feats = mw.preprocess_batch(batch.id_type_features, sc.schema)
    slot = sc.schema.get_slot("item")
    fi = [f.name for f in batch.id_type_features].index("item")
    gj = np.asarray(g_joint[fi], np.float32)
    gc = np.asarray(g_click[fi], np.float32)
    gv = np.asarray(g_conv[fi], np.float32)
    agg_joint = mw.aggregate_gradients(feats[fi], slot, gj)
    agg_split = (mw.aggregate_gradients(feats[fi], slot, gc)
                 + mw.aggregate_gradients(feats[fi], slot, gv))
    np.testing.assert_allclose(agg_joint, agg_split, rtol=1e-4,
                               atol=1e-5)


def test_multitask_labels_shape_and_tasks():
    sc = get_scenario("multitask", smoke=True)
    b = next(iter(sc.batches(64, 64)))
    assert b.labels[0].data.shape == (64, 2)
    assert sc.tasks == ("click", "convert")
    assert sc.loss_fn is not None


# --- scenario registry ---------------------------------------------------

def test_registry_roundtrip_all_scenarios():
    """Every registered scenario resolves, its stream matches its
    schema (names, batch sizes), and its model initializes and runs a
    forward pass on the stream's shapes."""
    import jax
    import jax.numpy as jnp

    assert set(scenario_names()) >= {"dlrm", "seqrec", "multitask"}
    for name in scenario_names():
        sc = get_scenario(name, smoke=True)
        b = next(iter(sc.batches(8, 8)))
        feat_names = [f.name for f in b.id_type_features]
        assert sorted(feat_names) == sorted(sc.schema.feature_names)
        assert b.non_id_type_features[0].data.shape == (8, sc.num_dense)
        for rf in sc.ragged_features:
            assert rf in feat_names
        # model forward on schema-shaped inputs (pooled slots = (bs, d))
        model = sc.model()
        non_id = [jnp.asarray(b.non_id_type_features[0].data)]
        emb = [jnp.zeros((8, sc.schema.get_slot(f.name).dim),
                         jnp.float32)
               for f in b.id_type_features]
        params = model.init(jax.random.key(0), non_id, emb)
        pred = model.apply(params, non_id, emb)
        assert pred.shape[0] == 8


def test_registry_unknown_scenario_raises():
    with pytest.raises(KeyError):
        get_scenario("nope")


def test_registry_honors_workload_knobs(monkeypatch):
    monkeypatch.setenv("PERSIA_WORKLOAD_SEED", "42")
    monkeypatch.setenv("PERSIA_WORKLOAD_ALPHA", "1.25")
    sc = get_scenario("dlrm", smoke=True)
    assert sc.seed == 42
    a42 = next(iter(sc.batches(32, 32))).to_bytes()
    monkeypatch.setenv("PERSIA_WORKLOAD_SEED", "43")
    sc2 = get_scenario("dlrm", smoke=True)
    assert sc2.seed == 43
    assert next(iter(sc2.batches(32, 32))).to_bytes() != a42


# --- planner predicted-vs-measured delta ---------------------------------

def test_planner_report_measured_hit_rate_delta():
    snap = {
        "enabled": True,
        "total": 1000,
        "tables": {
            "16": {"total": 1000, "unique_est": 100.0,
                   "topk": [[int(s), 50, 0] for s in range(1, 11)]},
        },
    }
    doc = hot.planner_report(snap, hbm_bytes=100 * 16 * 4)
    assert "measured_overall_hit_rate" not in doc
    doc = hot.planner_report(snap, hbm_bytes=100 * 16 * 4,
                             measured_hit_rate=0.5)
    assert doc["measured_overall_hit_rate"] == 0.5
    assert doc["hit_rate_delta"] == pytest.approx(
        doc["expected_overall_hit_rate"] - 0.5, abs=1e-6)


# --- dataloader cursor determinism across restart (PR 19) ----------------

@pytest.mark.parametrize("name", ["dlrm", "seqrec", "multitask"])
def test_cursor_resume_replays_exact_batch_suffix(name):
    """The data leg of whole-job crash safety: same seed + saved cursor
    must reproduce the exact (byte-identical) batch sequence the dead
    incarnation would have trained — for every zoo generator."""
    from persia_tpu.data.dataloader import ResumableDataset

    sc = get_scenario(name, smoke=True)
    bs, n, trained = 32, 6, 4

    def factory(seed):
        return sc.batches(n * bs, bs, seed=seed)

    full = [b.to_bytes() for b in ResumableDataset(factory, seed=7)]
    assert len(full) == n

    # incarnation 1: the prefetch pipeline ran AHEAD of the optimizer
    # (produced 6, trained 4) when the process died — the cursor must
    # name the trained position, not the produced one
    ds = ResumableDataset(factory, seed=7)
    produced = [b.to_bytes() for b in ds]
    assert produced == full and ds.produced == n
    cur = ds.cursor(trained=trained)
    assert cur == {"seed": 7, "consumed": trained}

    # incarnation 2: nothing but {seed, consumed} -> exact suffix,
    # including the batches that sat in the pipeline at death
    resumed = ResumableDataset.from_cursor(factory, cur)
    assert [b.to_bytes() for b in resumed] == full[trained:]


def test_cursor_resume_across_process_restart(tmp_path):
    """Same contract across an actual process boundary: a fresh
    interpreter given only the cursor reproduces the suffix digest."""
    import hashlib
    import json
    import os
    import subprocess
    import sys

    from persia_tpu.data.dataloader import ResumableDataset

    sc = get_scenario("dlrm", smoke=True)
    full = [b.to_bytes()
            for b in ResumableDataset(lambda s: sc.batches(4 * 32, 32, seed=s),
                                      seed=11)]
    cur = {"seed": 11, "consumed": 2}
    want = hashlib.sha256(b"".join(full[2:])).hexdigest()

    prog = (
        "import hashlib, json, sys\n"
        "from persia_tpu.workloads import get_scenario\n"
        "from persia_tpu.data.dataloader import ResumableDataset\n"
        "cur = json.loads(sys.argv[1])\n"
        "sc = get_scenario('dlrm', smoke=True)\n"
        "ds = ResumableDataset(lambda s: sc.batches(4 * 32, 32, seed=s)"
        ", seed=cur['seed'], start=cur['consumed'])\n"
        "h = hashlib.sha256(b''.join(b.to_bytes() for b in ds))\n"
        "print(h.hexdigest())\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", prog, json.dumps(cur)],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == want


# --- every scenario end to end through the hybrid stack --------------------


def _planner_prediction_holds_on_fresh_traffic(scenario, holders):
    """The hotness planner's predicted device-cache hit rate, fitted
    from the telemetry the TRAINING traffic produced, against the hit
    rate a frequency-admitted device mapper measures on FRESH traffic
    from the same generator (seeds the sketches never saw)."""
    from persia_tpu import hotness as hot
    from persia_tpu.worker.device_cache import TieredSignSlotMap

    snap = hot.merge_snapshots([h.hotness_snapshot() for h in holders])
    assert snap.get("enabled"), "hotness sketches never armed"
    # budget ~35% of the estimated unique fp32 rows: deep enough that
    # the zipf head fits, shallow enough that the hit rate is a real
    # number (not 1.0) the prediction could get wrong
    full_bytes = sum(
        float(t.get("unique_est") or 1.0) * int(tbl) * 4
        for tbl, t in snap["tables"].items())
    hbm_bytes = max(1 << 12, int(0.35 * full_bytes))
    plan = hot.planner_report(snap, hbm_bytes=hbm_bytes)
    # one frequency-admitted mapper per planner table (PS tables are
    # keyed by dim), sized at the PLAN's hot_rows
    mappers = {t["table"]: TieredSignSlotMap(max(int(t["hot_rows"]), 1))
               for t in plan["tables"]}
    bs = scenario.bench_batch_size

    def replay(passes, first_seed):
        for p in range(passes):
            for b in scenario.batches(8 * bs, bs, seed=first_seed + p,
                                      requires_grad=False):
                by_dim = {}
                for f in b.id_type_features:
                    d = str(scenario.schema.get_slot(f.name).dim)
                    by_dim.setdefault(d, []).append(f.signs)
                for d, signs in by_dim.items():
                    if d in mappers:
                        mappers[d].assign(np.concatenate(signs))

    replay(2, scenario.seed + 5000)
    c0 = {d: (m.hits, m.misses) for d, m in mappers.items()}
    replay(2, scenario.seed + 5002)
    dh = sum(m.hits - c0[d][0] for d, m in mappers.items())
    dm = sum(m.misses - c0[d][1] for d, m in mappers.items())
    delta = hot.planner_report(
        snap, hbm_bytes=hbm_bytes,
        measured_hit_rate=dh / max(dh + dm, 1))["hit_rate_delta"]
    assert abs(delta) <= 0.20, (
        f"planner hit-rate delta {delta:+.3f}: the telemetry-driven "
        f"capacity plan does not survive traffic it did not generate")


@pytest.mark.parametrize("name", ["dlrm", "seqrec", "multitask"])
def test_scenario_trains_through_hybrid_stack(name):
    """generator -> worker middleware -> PS holders -> jitted dense step
    -> sparse update: the loss must actually fall and the held-out AUC
    (disjoint seed, same hidden task) must clear the scenario's floor —
    "the pipeline runs but nothing learns" fails here. For dlrm the
    training run's own telemetry must also predict the device hit rate
    of fresh traffic."""
    import jax

    from persia_tpu.workloads import evaluate_auc
    from tests.fleet_support import scenario_stack

    sc = get_scenario(name, smoke=True)
    bs = sc.bench_batch_size
    ctx, worker, holders = scenario_stack(sc, hotness=(name == "dlrm"))
    losses = []
    try:
        with ctx:
            for b in sc.batches(120 * bs, bs):
                loss, _ = ctx.train_step(b)
                losses.append(float(loss))
            jax.block_until_ready(loss)
            aucs = evaluate_auc(ctx, sc, num_samples=2048,
                                batch_size=min(bs, 512))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]), \
            "loss did not fall — the scenario is not training"
        assert min(aucs.values()) >= sc.auc_gate, (aucs, sc.auc_gate)
        if name == "dlrm":
            _planner_prediction_holds_on_fresh_traffic(sc, holders)
    finally:
        worker.close()


def test_ragged_free_traffic_keeps_the_legacy_wire():
    """A schema that spells the ``pooling`` field out (all-"sum") and
    the same schema as a pre-zoo config would build it (no pooling keys
    at all) produce byte-identical lookup framing AND serve identical
    RPC counts for identical cycles over real PS services."""
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.rpc import pack_arrays_sg
    from persia_tpu.service.ps_service import PsClient, PsService
    from persia_tpu.service.serialization import (
        pack_id_features,
        unpack_id_features,
    )
    from persia_tpu.worker.worker import EmbeddingWorker

    sc = get_scenario("dlrm", smoke=True)
    assert not sc.ragged_features

    def join_sg(b):
        return b if isinstance(b, (bytes, bytearray)) else b"".join(
            bytes(x) for x in b)

    legacy_schema = EmbeddingSchema.from_dict({"slots_config": {
        n: {"dim": s.dim, "sample_fixed_size": s.sample_fixed_size,
            "embedding_summation": s.embedding_summation}
        for n, s in sc.schema.slots_config.items()}})
    batch = next(iter(sc.batches(64, 64, requires_grad=False)))
    # the loader wire: id-feature framing carries exactly the legacy
    # meta (names only) — no pooling rider crept in
    meta, _feats = unpack_id_features(
        pack_id_features(batch.id_type_features))
    assert set(meta) == {"names"}

    first = batch.id_type_features[0]
    g_signs = np.sort(np.unique(first.signs))[:32].astype(np.uint64)
    dim = sc.schema.get_slot(first.name).dim
    bs = min(sc.bench_batch_size, 256)
    svcs, stacks = [], {}
    try:
        for k, schema in (("zoo", sc.schema), ("legacy", legacy_schema)):
            svc = PsService(EmbeddingHolder(200_000, 4), port=0)
            svc.server.serve_background()
            svcs.append(svc)
            cli = PsClient(svc.addr)
            cli.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
            cli.register_optimizer({
                "type": "adagrad", "lr": 0.05, "initialization": 0.01,
                "g_square_momentum": 1.0, "vectorwise_shared": False})
            stacks[k] = (EmbeddingWorker(schema, [cli]), cli)
        served, framing = {}, {}
        for k, (w, cli) in stacks.items():
            served0 = cli.health()["served_rpcs"]
            for b in sc.batches(2 * bs, bs, requires_grad=True):
                ref, lookup = w.lookup_direct_training(b.id_type_features)
                w.update_gradients(ref, {
                    f.name: np.ones_like(lookup[f.name].embeddings)
                    for f in b.id_type_features})
            served[k] = cli.health()["served_rpcs"] - served0
            # the client's REAL lookup framing (its own _lookup_meta,
            # not a hand-built dict: a future meta rider shows up here)
            framing[k] = join_sg(cli._pack(cli._lookup_meta(dim, True),
                                           [g_signs]))
        assert served["zoo"] == served["legacy"]
        assert framing["zoo"] == framing["legacy"] == join_sg(
            pack_arrays_sg({"dim": dim, "training": True}, [g_signs]))
    finally:
        for _w, cli in stacks.values():
            cli.shutdown()
        for s in svcs:
            s.stop()
