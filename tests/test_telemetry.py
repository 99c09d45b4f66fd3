"""Workload-telemetry tests: Space-Saving/count-min property bounds,
merge algebra (exact commutativity/associativity), holder wiring with a
zero-overhead disabled path, the hotness RPC + /hotness sidecar +
/fleet/hotness merge surfaces, gradient-staleness and serving-freshness
accounting, the byte-identical-when-off wire pin (served-request
counts + structural framing), the bisect histogram with purpose-shaped
buckets, the table-labeled PS miss counters, and a persialint-clean
gate over the new lock-owning sketch classes."""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from persia_tpu import hotness as hot
from persia_tpu.hashing import farmhash64_np
from persia_tpu.metrics import (
    AGE_BUCKETS,
    COUNT_BUCKETS,
    STEP_BUCKETS,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from persia_tpu.ps.store import EmbeddingHolder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DIM = 16


def _zipf_stream(rng, vocab, n, alpha=1.05):
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -alpha
    cdf = np.cumsum(p / p.sum())
    return (np.searchsorted(cdf, rng.random(n)) + 1).astype(np.uint64)


def _configured_holder(hotness, **kw):
    h = EmbeddingHolder(1 << 20, 8, hotness=hotness, **kw)
    h.configure("bounded_uniform", {"lower": -0.01, "upper": 0.01})
    h.register_optimizer({
        "type": "adagrad", "lr": 0.02, "initialization": 0.1,
        "g_square_momentum": 1.0, "vectorwise_shared": False})
    return h


# --- sketch properties -----------------------------------------------------


def test_spacesaving_exact_below_capacity():
    ss = hot.SpaceSaving(64)
    rng = np.random.default_rng(0)
    stream = rng.integers(1, 33, size=2000, dtype=np.uint64)
    for s in stream:
        ss.offer(int(s))
    true = np.bincount(stream.astype(np.int64), minlength=40)
    snap = ss.snapshot()
    assert len(snap) == len(set(stream.tolist()))
    for s, (c, e) in snap.items():
        assert c == true[s] and e == 0


def test_spacesaving_bounds_sequential():
    """The classic invariants on a skewed stream: every tracked count
    overestimates by at most its recorded error, and every sign whose
    true frequency clears total/k is tracked."""
    rng = np.random.default_rng(1)
    vocab, k = 3000, 256
    stream = _zipf_stream(rng, vocab, 30_000)
    ss = hot.SpaceSaving(k)
    for s in stream:
        ss.offer(int(s))
    true = np.bincount(stream.astype(np.int64), minlength=vocab + 2)
    snap = ss.snapshot()
    assert len(snap) == k
    for s, (c, e) in snap.items():
        assert c >= true[s], (s, c, true[s])
        assert c - e <= true[s], (s, c, e, true[s])
    guarantee = len(stream) / k
    tracked = set(snap)
    for s in np.nonzero(true > guarantee)[0]:
        assert int(s) in tracked, (s, true[s], guarantee)


def test_spacesaving_batched_with_cm_filter_bounds():
    """The vectorized batch path (dedup -> CM admission filter ->
    batched eviction) keeps the same invariants as the sequential
    algorithm."""
    rng = np.random.default_rng(2)
    vocab, k = 5000, 512
    stream = _zipf_stream(rng, vocab, 120_000)
    ss = hot.SpaceSaving(k)
    cm = hot.CountMinSketch(8192, 4)
    for i in range(0, len(stream), 16384):
        uniq, cnts = np.unique(stream[i:i + 16384], return_counts=True)
        est = cm.add_and_estimate(farmhash64_np(uniq), cnts)
        ss.offer_many(uniq, cnts, est)
    true = np.bincount(stream.astype(np.int64), minlength=vocab + 2)
    snap = ss.snapshot()
    for s, (c, e) in snap.items():
        assert c >= true[s], (s, c, true[s])
        assert c - e <= true[s], (s, c, e, true[s])
    # heavy hitters survive the batch path (small slack: the admission
    # filter trades churn for a near-boundary straggler or two)
    top50 = set(np.argsort(true)[::-1][:50].tolist())
    tracked = set(snap)
    assert len(top50 & tracked) >= 48


def test_countmin_upper_bound():
    rng = np.random.default_rng(3)
    stream = _zipf_stream(rng, 2000, 50_000)
    cm = hot.CountMinSketch(4096, 4)
    uniq, cnts = np.unique(stream, return_counts=True)
    cm.add(farmhash64_np(uniq), cnts)
    est = cm.estimate(farmhash64_np(uniq))
    assert (est >= cnts).all()
    # collision noise stays well under eps*total for width 4096
    assert (est - cnts).max() <= 8 * len(stream) / 4096


def test_hll_empty_batch_is_noop():
    """An all-empty sparse slot reaches add_hashed with a zero-length
    array via dedup_feature — the sort+reduceat rewrite must keep the
    old np.maximum.at no-op behavior instead of raising."""
    from persia_tpu.worker.monitor import HyperLogLog

    hll = HyperLogLog(8)
    hll.add_hashed(np.empty(0, dtype=np.uint64))
    assert hll.estimate() == 0.0
    hll.add_signs(np.arange(1, 100, dtype=np.uint64))
    before = hll.registers.copy()
    hll.add_hashed(np.empty(0, dtype=np.uint64))
    np.testing.assert_array_equal(hll.registers, before)


def test_countmin_rejects_bad_geometry():
    with pytest.raises(ValueError):
        hot.CountMinSketch(0, 4)
    with pytest.raises(ValueError):
        hot.SpaceSaving(0)


# --- merge algebra ---------------------------------------------------------


def _tracker_snapshot(seed, tables=(16,), shards=4, n=20_000, offset=0):
    rng = np.random.default_rng(seed)
    tr = hot.HotnessTracker(shards, topk=64, cm_width=1024, cm_depth=3)
    for t in tables:
        tr.observe(t, _zipf_stream(rng, 2000, n) + np.uint64(offset))
    return tr.snapshot()


def test_merge_commutative_and_associative():
    """Snapshot merging is EXACT set algebra: integer sums in float64
    cells, register max, pointwise top-K union — so any merge order
    produces the identical document."""
    a = _tracker_snapshot(1)
    b = _tracker_snapshot(2, offset=5000)          # disjoint signs
    c = _tracker_snapshot(3, tables=(16, 32))      # overlapping signs
    ab = hot.merge_snapshots([a, b])
    ba = hot.merge_snapshots([b, a])
    assert ab == ba
    left = hot.merge_snapshots([hot.merge_snapshots([a, b]), c])
    right = hot.merge_snapshots([a, hot.merge_snapshots([b, c])])
    assert left == right
    assert ab["total"] == a["total"] + b["total"]
    # disabled snapshots are identity elements
    assert hot.merge_snapshots([a, hot.disabled_snapshot()]) == \
        hot.merge_snapshots([a])


def test_merge_rejects_mixed_geometry():
    a = _tracker_snapshot(1)
    tr = hot.HotnessTracker(4, topk=32, cm_width=512, cm_depth=2)
    tr.observe(16, np.arange(1, 100, dtype=np.uint64))
    with pytest.raises(ValueError):
        hot.merge_snapshots([a, tr.snapshot()])


def test_coverage_curve_monotone_bounded():
    snap = _tracker_snapshot(4, n=50_000)
    curve = hot.coverage_curve(snap["tables"]["16"])
    covs = [pt["coverage"] for pt in curve]
    assert all(0.0 <= c <= 1.0 for c in covs)
    assert covs == sorted(covs)
    assert covs[-1] == 1.0  # full-set coverage is everything
    rep = hot.table_report(snap["tables"]["16"])
    assert rep["zipf_alpha"] is None or rep["zipf_alpha"] > 0
    plan = hot.planner_report(snap, hbm_bytes=1 << 16)
    assert 0.0 <= plan["expected_overall_hit_rate"] <= 1.0
    assert plan["tables"][0]["hot_rows"] >= 0


# --- holder wiring ---------------------------------------------------------


def test_sketch_recall_and_coverage_error_against_exact_counts():
    """Sketch accuracy under zipf(1.05) traffic through a real armed
    holder, against exact counts of the same stream: top-100 recall
    >= 0.95 and the coverage curve within 2 points of the truth at
    every grid fraction."""
    rng = np.random.default_rng(7)
    vocab, dim = 1 << 14, 16
    holder = _configured_holder(True)
    exact = np.zeros(vocab + 1, dtype=np.int64)
    for _ in range(16):
        signs = _zipf_stream(rng, vocab, 2048).clip(max=vocab)
        np.add.at(exact, signs.astype(np.int64), 1)
        holder.lookup(signs, dim, training=True)
    table = holder.hotness_snapshot()["tables"][str(dim)]
    n_eval = 100
    # tie-aware recall: a sketch pick whose TRUE count reaches the true
    # 100th count is a correct heavy hitter even if argsort broke the
    # tie the other way
    kth_count = np.sort(exact)[::-1][n_eval - 1]
    picks = [s for s, _c, _e in table["topk"][:n_eval]]
    recall = sum(1 for s in picks
                 if s <= vocab and exact[s] >= kth_count) / n_eval
    assert recall >= 0.95, recall
    true_counts = np.sort(exact[exact > 0])[::-1].astype(np.float64)
    prefix = np.cumsum(true_counts)
    n_uniq = len(true_counts)
    for pt in hot.coverage_curve(table):
        n_true = max(1, min(int(round(pt["frac"] * n_uniq)), n_uniq))
        err = abs(pt["coverage"] - prefix[n_true - 1] / prefix[-1])
        assert err <= 0.02, (pt, err)


def test_holder_disabled_path_is_off():
    h = _configured_holder(hotness=False)
    assert h.hotness is None
    h.lookup(np.arange(1, 100, dtype=np.uint64), DIM, True)
    assert h.hotness_snapshot() == hot.disabled_snapshot()


def test_holder_armed_observes_lookups():
    h = _configured_holder(hotness=True)
    h2 = _configured_holder(hotness=False)
    rng = np.random.default_rng(5)
    rng2 = np.random.default_rng(5)
    seen = 0
    for _ in range(4):
        signs = _zipf_stream(rng, 1000, 2048)
        h.lookup(signs, DIM, True)
        h2.lookup(_zipf_stream(rng2, 1000, 2048), DIM, True)
        seen += len(signs)
    snap = h.hotness_snapshot()
    assert snap["enabled"]
    assert snap["total"] == seen
    assert snap["tables"][str(DIM)]["total"] == seen
    # armed and disabled holders return identical embeddings (init is
    # seeded by sign, so same op sequence -> same state either way)
    signs = _zipf_stream(np.random.default_rng(5), 1000, 2048)
    np.testing.assert_array_equal(h.lookup(signs, DIM, False),
                                  h2.lookup(signs, DIM, False))


def test_holder_miss_counters_labeled_by_table():
    reg = default_registry()
    c_idx = reg.counter("ps_index_miss_total", {"table": str(DIM)})
    c_grad = reg.counter("ps_gradient_id_miss_total", {"table": str(DIM)})
    i0, g0 = c_idx.value, c_grad.value
    h = _configured_holder(hotness=False)
    miss_signs = np.arange(10_001, 10_033, dtype=np.uint64)
    h.lookup(miss_signs, DIM, False)  # eval lookups: all miss
    assert c_idx.value - i0 == len(miss_signs)
    h.update_gradients(miss_signs,
                       np.zeros((len(miss_signs), DIM), np.float32), DIM)
    assert c_grad.value - g0 == len(miss_signs)
    # the aggregate health-RPC ints agree
    assert h.index_miss_count == len(miss_signs)
    assert h.gradient_id_miss_count == len(miss_signs)


# --- metrics satellite -----------------------------------------------------


def test_histogram_bisect_matches_le_semantics():
    hgram = Histogram(buckets=(1, 5, 10))
    for v in (0, 1, 1.5, 5, 7, 10, 11, 1000):
        hgram.observe(v)
    counts, hsum, total = hgram.snapshot_full()
    assert counts == [2, 2, 2, 2]  # {0,1} {1.5,5} {7,10} {11,1000}
    assert total == 8 and hsum == sum((0, 1, 1.5, 5, 7, 10, 11, 1000))


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        Histogram(buckets=(5, 1, 10))
    with pytest.raises(ValueError):
        Histogram(buckets=(1, 1, 2))


def test_registry_histogram_custom_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("staleness_steps_test", buckets=STEP_BUCKETS)
    assert h.buckets == STEP_BUCKETS
    h.observe(3)
    h.observe(700)
    text = reg.render()
    assert 'le="4"' in text and 'le="1024"' in text
    # purpose-shaped constants are strictly increasing
    for b in (STEP_BUCKETS, AGE_BUCKETS, COUNT_BUCKETS):
        assert list(b) == sorted(set(b))


# --- service surfaces ------------------------------------------------------


def _mk_service(hotness, **kw):
    from persia_tpu.service.ps_service import PsService

    svc = PsService(_configured_holder(hotness=hotness), **kw)
    svc.server.serve_background()
    return svc


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def test_hotness_rpc_and_sidecar_endpoint():
    from persia_tpu.service.ps_service import PsClient

    svc = _mk_service(True, http_port=0)
    try:
        cli = PsClient(svc.addr, hotness=True)
        signs = _zipf_stream(np.random.default_rng(6), 500, 1024)
        cli.lookup(signs, DIM, True)
        snap = cli.hotness()
        assert snap["enabled"] and snap["total"] == len(signs)
        base = f"http://{svc.http.addr}/hotness"
        summary = _get_json(base)
        table = summary["tables"][str(DIM)]
        assert table["coverage"] and "top_rows" in table
        full = _get_json(base + "?full=1")
        assert "cm" in full["tables"][str(DIM)]
        assert full["total"] == len(signs)
        doc = svc._health()
        assert doc["hotness_enabled"] is True
        cli.shutdown()
    finally:
        svc.stop()


def test_hotness_disabled_sidecar_answers_marker():
    svc = _mk_service(False, http_port=0)
    try:
        doc = _get_json(f"http://{svc.http.addr}/hotness")
        assert doc["enabled"] is False
        assert svc._health()["hotness_enabled"] is False
    finally:
        svc.stop()


def test_fleet_hotness_merge_totals():
    from persia_tpu.fleet import FleetMonitor
    from persia_tpu.service.ps_service import PsClient

    svcs = [_mk_service(True, http_port=0) for _ in range(2)]
    try:
        rng = np.random.default_rng(7)
        for i, svc in enumerate(svcs):
            cli = PsClient(svc.addr, hotness=True)
            cli.lookup(_zipf_stream(rng, 400, 512), DIM, True)
            cli.shutdown()
        monitor = FleetMonitor(targets=[
            {"service": f"ps{i}", "http_addr": svc.http.addr,
             "replica": i} for i, svc in enumerate(svcs)])
        try:
            monitor.scrape_once()
            shard_totals = [
                _get_json(f"http://{svc.http.addr}/hotness?full=1")["total"]
                for svc in svcs]
            doc = monitor.fleet_hotness(hbm_bytes=1 << 20)
            assert doc["total"] == sum(shard_totals) == 1024
            assert doc["tables"][str(DIM)]["coverage"]
            assert doc["planner"]["hbm_bytes"] == 1 << 20
            assert len(doc["sources"]) == 2
        finally:
            monitor.stop()
    finally:
        for svc in svcs:
            svc.stop()


# --- wire pins -------------------------------------------------------------


def _join_sg(b):
    return b if isinstance(b, (bytes, bytearray)) else b"".join(
        bytes(x) for x in b)


def test_wire_byte_identical_with_telemetry_off():
    """Telemetry off: request framing is byte-for-byte the legacy
    protocol (no `hv`/`hver` meta keys), and identical op sequences
    serve identical RPC counts whether the server's sketches are armed
    or not — telemetry never adds wire traffic."""
    from persia_tpu.rpc import pack_arrays_sg
    from persia_tpu.service.ps_service import PsClient

    svc_on = _mk_service(True)
    svc_off = _mk_service(False)
    try:
        off = PsClient(svc_off.addr, hotness=False)
        signs = np.arange(1, 257, dtype=np.uint64)
        grads = np.zeros((256, DIM), np.float32)
        assert _join_sg(off._pack(off._lookup_meta(DIM, True), [signs])) \
            == _join_sg(pack_arrays_sg({"dim": DIM, "training": True},
                                       [signs]))
        assert _join_sg(off._update_payload(signs, grads, DIM)) == \
            _join_sg(pack_arrays_sg({"dim": DIM}, [signs, grads]))

        # served-request-count pin: same ops, same counts, armed or not
        clients = {"on": PsClient(svc_on.addr, hotness=False),
                   "off": off}
        served0 = {k: {"on": svc_on, "off": svc_off}[k].server.health()
                   ["served_rpcs"] for k in clients}
        for k, cli in clients.items():
            out = cli.lookup(signs, DIM, True)
            cli.update_gradients(signs, out * 0.01, DIM)
        served1 = {k: {"on": svc_on, "off": svc_off}[k].server.health()
                   ["served_rpcs"] for k in clients}
        assert (served1["on"] - served0["on"]
                == served1["off"] - served0["off"] == 2)
        for cli in clients.values():
            cli.shutdown()
    finally:
        svc_on.stop()
        svc_off.stop()


def test_armed_client_meta_negotiates_down():
    """An armed client against an armed server learns the update
    version; the same client against a version-less reply simply never
    attaches `hver` (negotiate-down without a probe)."""
    from persia_tpu.service.ps_service import PsClient

    svc = _mk_service(True)
    try:
        cli = PsClient(svc.addr, hotness=True)
        assert cli._lookup_meta(DIM, True).get("hv") == 1
        assert "hver" not in cli._update_meta(DIM)  # nothing seen yet
        out = cli.lookup(np.arange(1, 65, dtype=np.uint64), DIM, True)
        cli.update_gradients(np.arange(1, 65, dtype=np.uint64),
                             out * 0.01, DIM)
        cli.lookup(np.arange(1, 65, dtype=np.uint64), DIM, True)
        assert cli._last_hver is not None
        assert cli._update_meta(DIM)["hver"] == cli._last_hver
        cli.shutdown()
    finally:
        svc.stop()


# --- staleness & freshness -------------------------------------------------


def test_ps_gradient_staleness_histogram():
    from persia_tpu.service.ps_service import PsClient

    svc = _mk_service(True)
    try:
        cli = PsClient(svc.addr, hotness=True)
        signs = np.arange(1, 129, dtype=np.uint64)
        out = cli.lookup(signs, DIM, True)
        # three updates after one lookup: staleness 0, 1, 2
        for _ in range(3):
            cli.update_gradients(signs, out * 0.01, DIM)
        counts, _s, total = svc._h_staleness.snapshot_full()
        assert total == 3
        # cumulative buckets: le=0 holds 1 (the first), le=2 holds all
        assert counts[0] == 1 and sum(counts) == 3
        cli.shutdown()
    finally:
        svc.stop()


def test_pipeline_staleness_histogram():
    from persia_tpu.pipeline import BackwardEngine

    class _FakeWorker:
        def update_gradients(self, ref_id, grads, loss_scale=1.0):
            pass

    h = default_registry().histogram("pipeline_gradient_staleness_steps")
    t0 = h.count
    eng = BackwardEngine(_FakeWorker(), num_workers=1)
    try:
        for i in range(4):
            eng.submit(i, {"slot": np.zeros((2, DIM), np.float32)})
        eng.flush(timeout=30)
    finally:
        eng.shutdown()
    assert h.count - t0 == 4


def test_inc_update_freshness_metrics(tmp_path):
    from persia_tpu.inc_update import (
        IncrementalUpdateDumper,
        IncrementalUpdateLoader,
    )
    from persia_tpu.service.ps_service import PsService

    src = _configured_holder(hotness=False)
    signs = np.arange(1, 33, dtype=np.uint64)
    src.lookup(signs, DIM, True)
    dumper = IncrementalUpdateDumper(src, str(tmp_path), buffer_size=10)
    dumper.commit(signs)
    dumper.flush()

    # construct the loader BEFORE touching the registry: the first
    # registration of a series sizes its buckets, and the loader is
    # the owner of these families
    dst = _configured_holder(hotness=False)
    loader = IncrementalUpdateLoader(dst, str(tmp_path))
    reg = default_registry()
    g = reg.gauge("inc_update_last_delay_sec")
    c = reg.counter("inc_update_packets_applied_total")
    hgram = reg.histogram("inc_update_freshness_lag_sec")
    c0, h0 = c.value, hgram.count
    loaded = loader.scan_once()
    assert loaded == len(signs)
    assert loader.packets_applied >= 1
    assert c.value - c0 >= 1 and hgram.count - h0 >= 1
    assert g.value == loader.last_delay_sec >= 0.0
    assert hgram.buckets == AGE_BUCKETS

    # the stall clock: rises while nothing applies (last_delay_sec
    # freezes at its last healthy value, so the SLO watches this one)
    since = reg.gauge("inc_update_sec_since_last_apply")
    assert since.value <= loader.sec_since_last_apply < 60.0
    loader._t_last_apply -= 700.0  # simulate a 700s-dead dumper
    assert loader.scan_once() == 0  # nothing new
    assert since.value >= 700.0

    svc = PsService(dst, inc_loader=loader)
    try:
        doc = svc._health()
        assert "inc_update_last_delay_sec" in doc
        assert doc["inc_update_sec_since_last_apply"] >= 700.0
        assert doc["inc_update_packets_applied"] == loader.packets_applied
    finally:
        svc.stop()


def test_default_slo_rules_cover_staleness_and_freshness():
    from persia_tpu.slos import SloEngine, default_rules

    names = {r.name for r in default_rules()}
    assert {"gradient_staleness_high", "serving_freshness_stale"} <= names
    # no data -> the new rules stay silent (unarmed fleets never page)
    eng = SloEngine(default_rules())
    eng.ingest("ps0", [("some_other_metric", {}, 1.0)])
    alerts = {(a["rule"]): a for a in eng.evaluate()}
    assert not alerts["gradient_staleness_high"]["firing"]
    assert not alerts["serving_freshness_stale"]["firing"]


# --- static analysis -------------------------------------------------------


def test_hotness_module_is_persialint_clean():
    """The new lock-owning sketch classes pass every persialint pass
    with no baseline and no suppressions."""
    from tools.persialint.core import run_lint

    result = run_lint([os.path.join(REPO, "persia_tpu", "hotness.py")],
                      baseline_path=None)
    assert not result.new, "\n".join(f.render() for f in result.new)
