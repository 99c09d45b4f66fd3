"""Online serving loop + multi-variant serving (persia_tpu.online,
persia_tpu.variants, the serving-side wiring): the versioned hot-row
cache upsert and its fetch-race regression, the write-rate governor,
delta apply across a live reshard epoch change (extends the
tests/test_reshard.py harness patterns), the per-replica freshness
health surface, the deterministic weighted variant split with
per-variant metric/SLO isolation, and the operator/fleet control
plane for variants."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from persia_tpu.config import EmbeddingSchema, uniform_slots
from persia_tpu.data.batch import (
    IDTypeFeature,
    IDTypeFeatureWithSingleID,
    NonIDTypeFeature,
    PersiaBatch,
)
from persia_tpu.inc_update import IncrementalUpdateDumper
from persia_tpu.online import DeltaSubscriber, RateGovernor
from persia_tpu.ps.store import EmbeddingHolder
from persia_tpu.routing import RoutingTable
from persia_tpu.serving import HotRowCache
from persia_tpu.variants import VariantRegistry, route_bucket
from persia_tpu.worker.worker import EmbeddingWorker

DIM = 8
N_SLOTS = 4
N_DENSE = 5


# --- HotRowCache: versioned upsert -------------------------------------


def _rows(n, val):
    return np.full((n, DIM), float(val), np.float32)


def test_cache_put_respects_delta_version_deterministic_interleaving():
    """The satellite regression, as a deterministic interleaving: a
    predict misses an EXPIRED resident row, a delta upsert lands while
    its fetch RPC is in flight, and the (older) fetched row arrives
    last. The version guard must keep the delta value — the stale
    cache slot can never resurrect the pre-delta row."""
    cache = HotRowCache(100, ttl_sec=0.05)
    signs = np.array([1, 2], np.uint64)
    cache.put(signs, DIM, _rows(2, 1.0))
    time.sleep(0.08)  # both entries TTL-expire
    out = np.zeros((2, DIM), np.float32)
    seen_ver = cache.version          # predict snapshots, then...
    miss = cache.gather(signs, DIM, out)
    assert list(miss) == [0, 1]       # ...misses both expired rows
    # the delta lands mid-flight (version bumps, TTL refreshed)
    assert cache.apply_delta(signs, DIM, _rows(2, 7.0)) == 2
    # the fetch returns the PRE-delta PS state — must be discarded
    cache.put(signs, DIM, _rows(2, 1.0), seen_ver=seen_ver)
    out2 = np.zeros((2, DIM), np.float32)
    assert len(cache.gather(signs, DIM, out2)) == 0
    np.testing.assert_array_equal(out2, _rows(2, 7.0))
    # a LATER fetch (fresh snapshot) may overwrite again
    cache.put(signs, DIM, _rows(2, 9.0), seen_ver=cache.version)
    out3 = np.zeros((2, DIM), np.float32)
    cache.gather(signs, DIM, out3)
    np.testing.assert_array_equal(out3, _rows(2, 9.0))


def test_cache_apply_delta_swaps_tuple_never_mutates_row():
    """Torn-read guard: the delta apply must REPLACE the entry tuple,
    never write into the stored row array — a gather that copied the
    old row keeps a complete pre-delta row."""
    cache = HotRowCache(10, ttl_sec=60.0)
    cache.put(np.array([5], np.uint64), DIM, _rows(1, 3.0))
    old_row = cache._od[(DIM, 5)][0]
    cache.apply_delta(np.array([5], np.uint64), DIM, _rows(1, 4.0))
    new_row = cache._od[(DIM, 5)][0]
    assert new_row is not old_row
    np.testing.assert_array_equal(old_row, _rows(1, 3.0)[0])
    np.testing.assert_array_equal(new_row, _rows(1, 4.0)[0])


def test_cache_apply_delta_refreshes_ttl_atomically():
    """No TTL-expiry dependence: a delta-applied row is servable past
    its original expiry (version and TTL stamp travel in one tuple)."""
    cache = HotRowCache(10, ttl_sec=0.2)
    s = np.array([9], np.uint64)
    cache.put(s, DIM, _rows(1, 1.0))
    time.sleep(0.1)
    cache.apply_delta(s, DIM, _rows(1, 2.0))
    time.sleep(0.15)  # past the ORIGINAL expiry, inside the refreshed
    out = np.zeros((1, DIM), np.float32)
    assert len(cache.gather(s, DIM, out)) == 0
    np.testing.assert_array_equal(out, _rows(1, 2.0))


def test_cache_apply_delta_never_inserts_or_evicts():
    cache = HotRowCache(3, ttl_sec=60.0)
    resident = np.array([1, 2, 3], np.uint64)
    cache.put(resident, DIM, _rows(3, 1.0))
    lru_order = list(cache._od)
    n = cache.apply_delta(np.array([2, 99, 100], np.uint64), DIM,
                          _rows(3, 5.0))
    assert n == 1                       # only the resident sign applied
    assert len(cache) == 3              # no insert, no evict
    assert list(cache._od) == lru_order  # recency untouched


# --- write-rate governor -------------------------------------------------


def test_governor_token_bucket_fake_clock():
    t = [0.0]
    slept = []

    def clock():
        return t[0]

    def sleep(s):
        slept.append(s)
        t[0] += s

    g = RateGovernor(1000, clock=clock, sleep=sleep)
    assert g.spend(500) == 0.0          # inside the 1s burst
    assert g.spend(500) == 0.0          # burst exhausted exactly
    w = g.spend(250)                    # must wait 0.25s of refill
    assert w == pytest.approx(0.25)
    assert slept == [pytest.approx(0.25)]
    assert g.throttled_sec == pytest.approx(0.25)
    t[0] += 10.0                        # long idle: bucket refills, capped
    assert g.spend(1000) == 0.0
    # disabled governor never sleeps
    g0 = RateGovernor(0, clock=clock, sleep=sleep)
    assert g0.spend(10**9) == 0.0
    assert len(slept) == 1


# --- delta subscriber ----------------------------------------------------


def _holder_with(signs, val):
    h = EmbeddingHolder(100_000, 2)
    for s in signs:
        h.set_entry(int(s), DIM, np.full(2 * DIM, float(val), np.float32))
    return h


_PKT_SEQ = iter(range(1, 10_000))


def _dump_packet(holder, inc_dir, signs, replica=0):
    d = IncrementalUpdateDumper(holder, inc_dir, buffer_size=1 << 30,
                                replica_index=replica)
    # each call builds a throwaway dumper; distinct seqs keep two
    # same-second flushes of one (replica, pid) from colliding on a
    # packet name (a real dumper's seq is process-persistent)
    d._seq = next(_PKT_SEQ)
    d.commit(np.asarray(signs, np.uint64))
    d.flush()


def test_subscriber_applies_resident_rows_only(tmp_path):
    inc_dir = str(tmp_path / "inc")
    signs = np.arange(1, 11, dtype=np.uint64)
    holder = _holder_with(signs, 4.0)
    cache = HotRowCache(100, ttl_sec=600.0)
    cache.put(signs[:4], DIM, _rows(4, 1.0))  # 4 of 10 resident
    sub = DeltaSubscriber(cache, inc_dir, rows_per_sec=0)
    assert sub.scan_once() == 0  # empty dir is fine
    _dump_packet(holder, inc_dir, signs)
    applied = sub.scan_once()
    assert applied == 4
    assert sub.packets_applied == 1
    assert sub.rows_skipped == 6
    assert sub.rows_filtered == 0
    out = np.zeros((4, DIM), np.float32)
    assert len(cache.gather(signs[:4], DIM, out)) == 0
    np.testing.assert_array_equal(out, _rows(4, 4.0))
    # no double-apply: the packet name is the dedup key
    assert sub.scan_once() == 0
    assert sub.packets_applied == 1
    h = sub.health()
    assert h["last_packet"].startswith("inc_")
    assert h["last_packet_seq"] >= 1
    assert h["last_packet_seq"] == int(h["last_packet"].split("_")[2])
    assert h["packets_applied"] == 1
    assert h["sec_since_last_apply"] < 5.0


def test_subscriber_routing_filter_across_epoch(tmp_path):
    """Routing-aware apply: a packet only lands rows its dumping
    replica OWNS under the live table (or the double-read
    predecessor). After a cutover's window closes, a donor's late
    packet for moved rows is filtered — it can no longer shadow the
    new owner — while the new owner's packet applies."""
    inc_dir = str(tmp_path / "inc")
    table = RoutingTable.uniform(2, slots_per_replica=16)
    signs = np.arange(1, 201, dtype=np.uint64)
    owners = table.replica_of(signs)
    mine0 = signs[owners == 0]
    cache = HotRowCache(1000, ttl_sec=600.0)
    cache.put(signs, DIM, _rows(len(signs), 1.0))
    window = {"table": table, "prev": None}
    sub = DeltaSubscriber(cache, inc_dir, rows_per_sec=0,
                          routing_fn=lambda: (window["table"],
                                              window["prev"]))
    # replica 0 dumps ALL signs; only its owned rows apply
    _dump_packet(_holder_with(signs, 2.0), inc_dir, signs, replica=0)
    assert sub.scan_once() == len(mine0)
    assert sub.rows_filtered == len(signs) - len(mine0)
    # cut over: move replica 0's slots to replica 2 (3-way table)
    new_assign = np.array(table.replica_of_slot, np.int32)
    new_assign[new_assign == 0] = 2
    new_table = table.derive(new_assign, 3)
    window["table"], window["prev"] = new_table, table
    # double-read window OPEN: the donor's flush still applies (its
    # packet may carry pre-cutover updates that must not be dropped)
    _dump_packet(_holder_with(mine0, 3.0), inc_dir, mine0, replica=0)
    assert sub.scan_once() == len(mine0)
    # window CLOSED: the donor's late stale packet is filtered...
    window["prev"] = None
    _dump_packet(_holder_with(mine0, 9.9), inc_dir, mine0, replica=0)
    assert sub.scan_once() == 0
    # ...and the new owner's packet applies
    _dump_packet(_holder_with(mine0, 5.0), inc_dir, mine0, replica=2)
    assert sub.scan_once() == len(mine0)
    out = np.zeros((len(mine0), DIM), np.float32)
    assert len(cache.gather(mine0, DIM, out)) == 0
    np.testing.assert_array_equal(out, _rows(len(mine0), 5.0))


def test_subscriber_live_reshard_no_drop_no_double(tmp_path):
    """The reshard-satellite end to end, test_reshard harness style:
    real PS services (inc-dumpers armed) behind a routed worker, a
    cache subscribing through the worker's routing window, and a live
    2→3 reshard mid-stream. Every packet applies exactly once, donor-
    and target-dumped packets both land (nothing dropped), and the
    cache converges to the post-reshard values."""
    from persia_tpu.reshard import ReshardController
    from persia_tpu.service.ps_service import PsClient, PsService

    inc_dir = str(tmp_path / "inc")
    holders = [EmbeddingHolder(200_000, 2) for _ in range(3)]
    dumpers = [IncrementalUpdateDumper(h, inc_dir, buffer_size=1 << 30,
                                       replica_index=i)
               for i, h in enumerate(holders)]
    services = [PsService(h, port=0, inc_dumper=d)
                for h, d in zip(holders, dumpers)]
    for s in services:
        s.server.serve_background()
    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        c.configure("bounded_uniform", {"lower": 0.0, "upper": 0.0},
                    admit_probability=1.0, weight_bound=1e9,
                    enable_weight_bound=False)
        c.register_optimizer({"type": "sgd", "lr": 1.0, "wd": 0.0})
    schema = EmbeddingSchema(slots_config=uniform_slots(
        ["slot_0", "slot_1"], dim=DIM))
    table = RoutingTable.uniform(2, slots_per_replica=16)
    worker = EmbeddingWorker(schema, clients[:2], routing=table)
    try:
        signs = np.arange(1, 129, dtype=np.uint64)
        feats = [IDTypeFeature(f"slot_{i}", [signs]) for i in range(2)]

        def train_once():
            ref, out = worker.lookup_direct_training(feats)
            worker.update_gradients(ref, {
                k: np.ones_like(v.embeddings) for k, v in out.items()})

        def flush_all():
            for d in dumpers:
                d.flush()

        cache = HotRowCache(10_000, ttl_sec=600.0)
        sub = DeltaSubscriber(
            cache, inc_dir, rows_per_sec=0,
            routing_fn=lambda: worker.routing_window)
        train_once()
        cache.put(signs, DIM, worker.lookup_signs(signs, DIM))
        # pre-reshard delta cycle
        train_once()
        flush_all()
        sub.scan_once()
        pre_packets = sub.packets_applied
        assert pre_packets > 0
        # live 2→3 reshard, then keep training on the new topology
        controller = ReshardController(clients[:2], table,
                                       workers=[worker],
                                       replay_settle_rows=32)
        new_table = controller.reshard_to(3, new_ps_clients=clients)
        assert worker.routing_epoch == new_table.epoch
        train_once()
        train_once()
        flush_all()
        sub.scan_once()
        controller.finalize(drain_sec=0)
        train_once()
        flush_all()
        sub.scan_once()
        # exactly once per packet directory — no drop, no double
        pkt_dirs = [n for n in os.listdir(inc_dir)
                    if n.startswith("inc_")]
        assert sub.packets_applied == len(pkt_dirs)
        assert sub.scan_once() == 0  # idempotent re-scan
        assert sub.packets_applied == len(pkt_dirs)
        # the newcomer's packets landed: replica 2 dumped at least once
        assert any("_r2_" in n for n in pkt_dirs)
        # cache rows match the authoritative post-reshard fleet view
        # (counting identity: both slots carry every sign, so each of
        # the 5 unit-gradient rounds contributes exactly -2 per row —
        # zero lost updates THROUGH the subscriber)
        out = np.zeros((len(signs), DIM), np.float32)
        assert len(cache.gather(signs, DIM, out)) == 0
        np.testing.assert_array_equal(out, worker.lookup_signs(signs,
                                                               DIM))
        np.testing.assert_array_equal(out, _rows(len(signs), -10.0))
    finally:
        worker.close()
        for s in services:
            s.stop()


# --- serving-side wiring (jax-backed) ------------------------------------


@pytest.fixture(scope="module")
def serving_world():
    from persia_tpu.models import DNN
    from persia_tpu.serving import build_state_template

    schema = EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{s}" for s in range(N_SLOTS)], dim=DIM))
    holders = [EmbeddingHolder(100_000, 2) for _ in range(2)]
    worker = EmbeddingWorker(schema, holders)
    worker.configure_parameter_servers(
        "bounded_uniform", {"lower": -0.1, "upper": 0.1}, 1.0, 10.0)
    worker.register_optimizer({"type": "sgd", "lr": 0.1, "wd": 0.0})
    model = DNN()
    state = build_state_template(model, schema, N_DENSE)
    return schema, worker, model, state


def _request(rows, seed):
    rng = np.random.default_rng(seed)
    return PersiaBatch(
        [IDTypeFeatureWithSingleID(
            f"slot_{s}",
            rng.integers(1, 3000, size=rows).astype(np.uint64))
         for s in range(N_SLOTS)],
        non_id_type_features=[NonIDTypeFeature(
            rng.normal(size=(rows, N_DENSE)).astype(np.float32))],
        requires_grad=False)


def test_server_healthz_surfaces_online_and_variants(serving_world,
                                                     tmp_path):
    from persia_tpu.serving import InferenceServer

    schema, worker, model, state = serving_world
    inc_dir = str(tmp_path / "inc")
    os.makedirs(inc_dir)
    server = InferenceServer(model, state, schema, worker=worker,
                             cache_rows=10_000, cache_ttl_sec=600.0)
    try:
        with pytest.raises(RuntimeError):
            # cacheless servers must refuse (nothing to upsert)
            InferenceServer(model, state, schema,
                            worker=worker).attach_delta_subscriber(
                                inc_dir)
    except ValueError:
        pass
    try:
        sub = server.attach_delta_subscriber(inc_dir,
                                             scan_interval_sec=30.0)
        doc = server._healthz()
        assert doc["online"]["sec_since_last_apply"] >= 0.0
        assert doc["online"]["last_packet_seq"] == 0
        assert doc["online"]["packets_applied"] == 0
        assert [v["name"] for v in doc["variants"]] == ["default"]
        assert doc["variants"][0]["default"] is True
        # a packet lands; the per-replica clock and seq move
        _dump_packet(_holder_with(np.array([7], np.uint64), 1.0),
                     inc_dir, [7])
        sub.scan_once()
        doc = server._healthz()
        assert doc["online"]["packets_applied"] == 1
        assert doc["online"]["last_packet_seq"] >= 1
        with pytest.raises(RuntimeError):
            server.attach_delta_subscriber(inc_dir)  # already attached
    finally:
        server.stop()


def test_variant_registry_deterministic_split():
    reg = VariantRegistry()
    reg.add("base", weight=0.75, default=True)
    reg.add("canary", weight=0.25)
    keys = [f"k{i}".encode() for i in range(500)]
    expected = reg.expected_split(keys)
    # pure function: replaying route() agrees key by key
    for k in keys:
        assert reg.route(key=k) == reg.route(key=k)
    assert sum(expected.values()) == len(keys)
    # a second registry with the same weights computes the SAME split
    # (what makes per-replica routing agree fleet-wide)
    reg2 = VariantRegistry()
    reg2.add("canary", weight=0.25)
    reg2.add("base", weight=0.75, default=True)
    assert reg2.expected_split(keys) == expected
    # share lands near the weights
    assert 0.15 < expected["canary"] / len(keys) < 0.35
    # no key -> default; explicit wins; draining leaves the pool but
    # still answers explicit requests
    assert reg.route() == "base"
    assert reg.route(key=b"x", explicit="canary") == "canary"
    reg.set_status("canary", "draining")
    assert all(reg.route(key=k) == "base" for k in keys[:50])
    assert reg.route(explicit="canary") == "canary"
    # promote flips the default and revives the variant
    reg.promote("canary")
    assert reg.default == "canary"
    assert reg.get("canary").status == "live"
    # the default is remove-protected
    with pytest.raises(ValueError):
        reg.remove("canary")
    reg.promote("base")
    reg.remove("canary")
    with pytest.raises(KeyError):
        reg.route(explicit="canary")
    assert route_bucket(b"stable-key", 1000) == route_bucket(
        b"stable-key", 1000)


def test_predict_variant_rpc_and_admin(serving_world):
    import jax

    from persia_tpu.serving import InferenceClient, InferenceServer

    schema, worker, model, state = serving_world
    b = _request(6, 42)
    worker.lookup_direct(b.id_type_features, training=True)
    state2 = state.replace(params=jax.tree_util.tree_map(
        lambda a: a + 0.25, state.params))
    server = InferenceServer(model, state, schema, worker=worker,
                             variant_name="base")
    server.add_variant("canary", state=state2, weight=1.0)
    server.serve_background()
    solo = InferenceServer(model, state2, schema, worker=worker)
    solo.serve_background()
    try:
        cl = InferenceClient(server.addr)
        sc = InferenceClient(solo.addr)
        # plain predict = default variant, empty meta (legacy wire)
        from persia_tpu.rpc import unpack_arrays

        resp = cl.client.call("predict", b.to_bytes())
        meta, (pred_base,) = unpack_arrays(resp)
        assert meta == {}
        # explicit variant serves ITS model (bit-match vs solo server)
        pred_canary, served = cl.predict_variant(b, variant="canary")
        assert served == "canary"
        np.testing.assert_array_equal(pred_canary, sc.predict(b))
        assert not np.array_equal(pred_canary, pred_base)
        # per-variant counters: isolated and exact
        doc = {v["name"]: v for v in server._variants_doc()}
        assert doc["base"]["requests"] == 1
        assert doc["canary"]["requests"] == 1
        # admin surface over RPC
        out = cl.variant_admin("list")
        assert {v["name"] for v in out["variants"]} == {"base", "canary"}
        cl.variant_admin("weight", name="canary", weight=0.5)
        assert server.variants.get("canary").weight == 0.5
        cl.variant_admin("promote", name="canary")
        assert server.variants.default == "canary"
        # plain predict now serves the promoted variant's model
        np.testing.assert_array_equal(cl.predict(b), pred_canary)
        cl.variant_admin("promote", name="base")
        cl.variant_admin("drain", name="canary")
        assert server.variants.get("canary").status == "draining"
        cl.variant_admin("remove", name="canary")
        assert "canary" not in server.variants
        with pytest.raises(Exception):
            cl.predict_variant(b, variant="canary")
    finally:
        server.stop()
        solo.stop()


def test_variant_split_over_microbatcher(serving_world):
    """The weighted split through the COALESCING path: merged batches
    are single-variant (grouping key includes the variant), so every
    response bit-matches its variant's serialized server."""
    import jax

    from persia_tpu.serving import InferenceClient, InferenceServer

    schema, worker, model, state = serving_world
    state2 = state.replace(params=jax.tree_util.tree_map(
        lambda a: a - 0.2, state.params))
    micro = InferenceServer(model, state, schema, worker=worker,
                            max_batch_rows=64, max_wait_us=4000,
                            variant_name="base")
    micro.add_variant("canary", state=state2, weight=0.5)
    micro.variants.set_weight("base", 0.5)
    micro.serve_background()
    plain = {
        "base": InferenceServer(model, state, schema, worker=worker),
        "canary": InferenceServer(model, state2, schema, worker=worker),
    }
    for s in plain.values():
        s.serve_background()
    reqs = [_request(4, 900 + i) for i in range(10)]
    for b in reqs:
        worker.lookup_direct(b.id_type_features, training=True)
    try:
        mc = InferenceClient(micro.addr)
        refs = {k: InferenceClient(s.addr) for k, s in plain.items()}
        errors = []

        def run(i):
            try:
                key = f"user{i}".encode()
                expect = micro.variants.route(key=key)
                got, served = mc.predict_variant(reqs[i], key=key)
                assert served == expect, (served, expect)
                np.testing.assert_array_equal(
                    got, refs[expect].predict(reqs[i]))
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[0]
    finally:
        micro.stop()
        for s in plain.values():
            s.stop()


# --- SLO isolation -------------------------------------------------------


def test_variant_slo_fires_per_variant():
    from persia_tpu.slos import SloEngine, default_rules

    rules = [r for r in default_rules() if r.name == "variant_degraded"]
    assert rules and rules[0].by_label == "variant"
    eng = SloEngine(rules=rules)

    def feed(t, a_deg, b_deg, reqs):
        eng.ingest("serving0", [
            ("inference_variant_degraded_total", {"variant": "a"}, a_deg),
            ("inference_variant_requests_total", {"variant": "a"}, reqs),
            ("inference_variant_degraded_total", {"variant": "b"}, b_deg),
            ("inference_variant_requests_total", {"variant": "b"}, reqs),
        ], t=t)

    feed(1000.0, 0, 0, 0)
    feed(1030.0, 50, 0, 100)  # variant a degrades hard, b stays clean
    alerts = {a["service"]: a for a in eng.evaluate(now=1030.0)
              if a["rule"] == "variant_degraded"}
    assert alerts["serving0[variant=a]"]["firing"] is True
    assert alerts["serving0[variant=b]"]["firing"] is False
    # the aggregate-masking failure this exists to prevent: had the
    # two variants been summed, 50/200 would still fire — but the
    # point is b must NOT page, and it doesn't


def test_serving_freshness_rule_covers_subscriber_series():
    """The stall-clock rule matches the subscriber's metric name, so
    a quiet serving subscriber fires serving_freshness_stale for ITS
    replica."""
    from persia_tpu.slos import SloEngine, default_rules

    rules = [r for r in default_rules()
             if r.name == "serving_freshness_stale"]
    eng = SloEngine(rules=rules)
    eng.ingest("serving1", [
        ("inc_update_sec_since_last_apply", {"consumer": "serving"},
         900.0),
    ], t=50.0)
    alerts = [a for a in eng.evaluate(now=50.0)
              if a["service"] == "serving1"]
    assert alerts and alerts[0]["firing"] is True


# --- fleet + operator control plane --------------------------------------


def test_fleet_variants_merge_and_skew():
    from persia_tpu.fleet import FleetMonitor, ScrapeTarget

    mon = FleetMonitor(targets=[])

    def fake_target(name, weight, default, requests):
        t = ScrapeTarget(name, "127.0.0.1:1")
        t.up = True
        t.last_health = {"variants": [
            {"name": "base", "weight": 1.0 - weight, "status": "live",
             "default": not default, "requests": 100},
            {"name": "canary", "weight": weight, "status": "live",
             "default": default, "requests": requests},
        ]}
        return t

    targets = [fake_target("serving0", 0.25, False, 10),
               fake_target("serving1", 0.25, False, 14)]
    mon.targets = lambda: targets  # type: ignore[method-assign]
    doc = mon.fleet_variants()
    by_name = {v["name"]: v for v in doc["variants"]}
    assert by_name["canary"]["requests"] == 24
    assert by_name["canary"]["replicas"] == 2
    assert not doc["skew"]
    # a half-landed weight push shows as skew
    targets[1] = fake_target("serving1", 0.5, False, 14)
    doc = mon.fleet_variants()
    assert doc["skew"]
    assert {v["name"] for v in doc["variants"]
            if v["skew"]} == {"canary", "base"}


def test_operator_variant_op_and_rest():
    from persia_tpu.k8s_operator import (
        FakeKubeApi,
        Operator,
        SchedulingServer,
    )

    spec = {"jobName": "job1",
            "roles": {"embeddingParameterServer": {"replicas": 1}}}
    calls = []

    def driver(job, op, payload, drv_spec):
        calls.append((job, op, payload.get("name")))
        return {"replicas_updated": 2}

    op = Operator(FakeKubeApi(), [spec], variant_driver=driver)
    ev = op.variant_op("job1", "promote", {"name": "canary"})
    assert ev["status"] == "done"
    assert calls == [("job1", "promote", "canary")]
    with pytest.raises(KeyError):
        op.variant_op("nope", "promote", {"name": "x"})
    with pytest.raises(ValueError):
        op.variant_op("job1", "explode", {"name": "x"})
    server = SchedulingServer(op)
    server.serve_background()
    try:
        body = json.dumps({"jobName": "job1", "op": "weight",
                           "name": "canary", "weight": 0.1}).encode()
        req = urllib.request.Request(
            f"http://{server.addr}/variants", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5) as resp:
            out = json.loads(resp.read())
        assert out["status"] == "done" and out["op"] == "weight"
        with urllib.request.urlopen(
                f"http://{server.addr}/variants", timeout=5) as resp:
            events = json.loads(resp.read())["events"]
        assert [e["op"] for e in events] == ["promote", "weight"]
    finally:
        server.stop()


def test_obs_http_variants_endpoint(serving_world):
    from persia_tpu.serving import InferenceServer

    schema, worker, model, state = serving_world
    server = InferenceServer(model, state, schema, worker=worker,
                             http_port=0, variant_name="prod")
    try:
        with urllib.request.urlopen(
                f"http://{server.http.addr}/variants", timeout=5) as r:
            doc = json.loads(r.read())
        assert doc["enabled"] is True
        assert [v["name"] for v in doc["variants"]] == ["prod"]
        with urllib.request.urlopen(
                f"http://{server.http.addr}/healthz", timeout=5) as r:
            hz = json.loads(r.read())
        assert hz["variants"][0]["name"] == "prod"
    finally:
        server.stop()


# --- the serving loop over real PS sockets --------------------------------


def test_serving_loop_split_oracle_freshness_and_idle_wire(tmp_path):
    """The online loop's pass/fail gates over real PS services (sockets,
    inc-dumper armed), one in-process worker over PsClients:

    - a trained probe update becomes servable through the delta
      subscriber alone (TTL effectively infinite), and the subscriber
      accounts for it;
    - a two-variant weighted split serves per-variant request counts
      EXACTLY as the deterministic oracle predicts, the server's own
      counters agree, explicit canary traffic never moves the base
      counter, and each variant bit-matches its single-model server;
    - with the subsystem off (no subscriber, one variant) the predict
      response meta is empty and cache-hot predicts plus an idle window
      add ZERO PS RPCs; a subscriber scan adds none either (the packet
      stream is disk, not RPC)."""
    import jax

    from persia_tpu.models import DLRM
    from persia_tpu.rpc import unpack_arrays
    from persia_tpu.service.ps_service import PsClient, PsService
    from persia_tpu.serving import (
        InferenceClient,
        InferenceServer,
        build_state_template,
    )
    from tests.fleet_support import time_limit, unit_update, wait_until

    inc_dir = str(tmp_path / "inc")
    os.makedirs(inc_dir)
    scan_sec = 0.15
    schema = EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{s}" for s in range(N_SLOTS)], dim=DIM))
    holders = [EmbeddingHolder(2_000_000, 8) for _ in range(2)]
    # huge buffer: the test controls flush timing
    dumpers = [IncrementalUpdateDumper(h, inc_dir, buffer_size=1 << 30,
                                       replica_index=i)
               for i, h in enumerate(holders)]
    services = [PsService(h, port=0, inc_dumper=d)
                for h, d in zip(holders, dumpers)]
    for s in services:
        s.server.serve_background()
    worker = EmbeddingWorker(
        schema, [PsClient(s.addr, circuit_breaker=False)
                 for s in services])
    worker.configure_parameter_servers(
        "bounded_uniform", {"lower": -0.01, "upper": 0.01}, 1.0, 1e9)
    worker.register_optimizer({"type": "sgd", "lr": 0.1, "wd": 0.0})
    model = DLRM(embedding_dim=DIM)
    state = build_state_template(model, schema, N_DENSE)
    probe = _request(8, 1)
    hot = [_request(32, 100 + i) for i in range(4)]
    for b in [probe] + hot:  # create every row the test will read
        worker.lookup_direct(b.id_type_features, training=True)
    probe_blob = probe.to_bytes()
    hot_blobs = [b.to_bytes() for b in hot]
    servers = []

    def serve(st=state, **kw):
        s = InferenceServer(model, st, schema, worker=worker, **kw)
        s.serve_background()
        servers.append(s)
        return s, InferenceClient(s.addr)

    def ps_served():
        return [s.server.health()["served_rpcs"] for s in services]

    try:
        with time_limit(120, "online serving loop"):
            # --- freshness through the subscriber alone ----------------
            online, oc = serve(cache_rows=500_000, cache_ttl_sec=3600.0)
            online.attach_delta_subscriber(inc_dir,
                                           scan_interval_sec=scan_sec)
            before = oc.predict_bytes(probe_blob).tobytes()
            unit_update(worker, probe.id_type_features)
            for d in dumpers:
                d.flush()
            wait_until(
                lambda: oc.predict_bytes(probe_blob).tobytes() != before,
                30, "probe update never became servable", interval=0.02)
            sub = online.online
            assert sub.packets_applied > 0 and sub.rows_applied > 0

            # --- two-variant weighted split ----------------------------
            var_server, vc = serve(cache_rows=200_000, cache_ttl_sec=600.0,
                                   variant_name="base")
            # the canary: same architecture, perturbed dense params, so
            # its predictions differ and the bit-match attributes
            canary_state = state.replace(params=jax.tree_util.tree_map(
                lambda a: a + 0.1, state.params))
            var_server.add_variant("canary", state=canary_state,
                                   weight=0.25)
            var_server.variants.set_weight("base", 0.75)
            keys = [f"user-{i}".encode() for i in range(80)]
            expected = var_server.variants.expected_split(keys)
            served = {}
            for k in keys:
                _, name = vc.predict_variant(probe_blob, key=k)
                served[name] = served.get(name, 0) + 1
            assert served == expected

            def counters():
                return {v["name"]: v["requests"]
                        for v in var_server._variants_doc()}

            assert counters() == expected
            for _ in range(20):
                _, name = vc.predict_variant(probe_blob, variant="canary")
                assert name == "canary"
            assert counters() == {"base": expected["base"],
                                  "canary": expected["canary"] + 20}
            for name, st in (("base", state), ("canary", canary_state)):
                _solo, sc = serve(st)
                got, served_by = vc.predict_variant(probe_blob,
                                                    variant=name)
                assert served_by == name
                np.testing.assert_array_equal(
                    got, sc.predict_bytes(probe_blob))

            # --- idle wire: subsystem off ------------------------------
            _off, fc = serve(cache_rows=200_000, cache_ttl_sec=3600.0)
            for blob in hot_blobs:  # warm pass fetches every row once
                fc.predict_bytes(blob)
            served0 = ps_served()
            metas = set()
            for i in range(30):
                meta, _arrs = unpack_arrays(fc.client.call(
                    "predict", hot_blobs[i % len(hot_blobs)]))
                metas.add(tuple(sorted(meta.items())))
            time.sleep(scan_sec * 3)  # an idle window
            assert ps_served() == served0
            assert metas == {()}
            sub.scan_once()
            assert ps_served() == served0
    finally:
        for s in servers + services:
            s.stop()
        worker.close()
