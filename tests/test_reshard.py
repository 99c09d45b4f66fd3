"""Elastic PS tier tests: the migration controller's zero-lost-updates
contract over a live 2→3 reshard under traffic, the freeze/bounce
protocol, the ownership-filtered incremental replay across a
shard-count change, hotness-balanced placement beating hash-even under
zipf(1.05), routing-aware checkpoints, the operator's scale sequencing
— and the crash-safety layer: the durable migration journal +
resume-after-SIGKILL (pre- and post-publish), fencing tokens and
idempotent retries on the reshard RPC surface, the donor freeze lease,
bounded reshard RPC deadlines, and the routing-edge races."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from persia_tpu.config import EmbeddingSchema, uniform_slots
from persia_tpu.data.batch import IDTypeFeature
from persia_tpu.reshard import (
    MigrationJournal,
    ReshardController,
    is_reshard_fenced,
    pack_rows,
    plan_assignment,
    unpack_rows,
)
from persia_tpu.routing import RoutingTable, is_routing_stale
from persia_tpu.worker.worker import EmbeddingWorker

DIM = 8


def _schema(n_slots=2):
    return EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{i}" for i in range(n_slots)], dim=DIM))


def _feature(name, signs):
    return IDTypeFeature(name, [np.asarray(signs, dtype=np.uint64)])


def _holder(capacity=200_000):
    from persia_tpu.ps.store import EmbeddingHolder

    h = EmbeddingHolder(capacity=capacity)
    return h


def _service(holder):
    from persia_tpu.service.ps_service import PsService

    svc = PsService(holder, port=0)
    svc.server.serve_background()
    return svc


def _arm(client):
    # zero init + unit-lr plain SGD: a row's value is exactly
    # -(number of unit-gradient updates it absorbed) — the counting
    # invariant every zero-lost-updates assertion reads off
    client.configure("bounded_uniform", {"lower": 0.0, "upper": 0.0},
                     admit_probability=1.0, weight_bound=1e9,
                     enable_weight_bound=False)
    client.register_optimizer({"type": "sgd", "lr": 1.0, "wd": 0.0})


def test_pack_unpack_rows_round_trip():
    rows = [(1, 4, np.arange(8, dtype=np.float32)),
            (2**63, 16, np.ones(16, np.float32))]
    back = unpack_rows(pack_rows(rows))
    assert [(s, d) for s, d, _v in back] == [(1, 4), (2**63, 16)]
    for (_, _, a), (_, _, b) in zip(rows, back):
        np.testing.assert_array_equal(a, b)
    assert unpack_rows(pack_rows([])) == []
    # the wire format itself: <Q count, then per row <QII sign, dim,
    # len and the f32 payload — the vectorized packer is held to it
    import struct

    want = struct.pack("<Q", len(rows)) + b"".join(
        struct.pack("<QII", s, d, len(v)) + v.tobytes()
        for s, d, v in rows)
    assert bytes(pack_rows(rows)) == want


def test_plan_assignment_moves_minimally():
    t = RoutingTable.uniform(2, slots_per_replica=8)  # 16 slots
    out = plan_assignment(t, 4)
    counts = np.bincount(out, minlength=4)
    assert counts.min() >= 3 and counts.max() <= 5
    # surviving replicas keep most of their slots: only the surplus
    # needed by the newcomers moves
    moved = int(np.count_nonzero(out != t.replica_of_slot))
    assert moved == int(counts[2] + counts[3])
    # scale-in: stranded slots re-deal, survivors keep everything
    t4 = t.derive(out, 4)
    back = plan_assignment(t4, 3)
    assert back.max() <= 2
    kept = np.count_nonzero(
        (back == t4.replica_of_slot) & (t4.replica_of_slot < 3))
    assert kept == int(np.count_nonzero(t4.replica_of_slot < 3))


def _zipf_snapshot(alpha=1.05, n_draws=200_000, vocab=100_000, seed=7):
    rng = np.random.default_rng(seed)
    ranks = np.minimum(rng.zipf(alpha, size=n_draws), vocab)
    # map rank -> a stable pseudo-random sign so slot placement is
    # hash-realistic, not rank-sequential
    with np.errstate(over="ignore"):
        signs = (ranks.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
                 ) >> np.uint64(1)
    uniq, counts = np.unique(signs, return_counts=True)
    order = np.argsort(counts)[::-1]
    topk = [[int(s), int(c), 0]
            for s, c in zip(uniq[order[:512]], counts[order[:512]])]
    return {
        "enabled": True,
        "total": int(n_draws),
        "tables": {str(DIM): {
            "total": int(n_draws),
            "unique_est": float(len(uniq)),
            "topk": topk,
        }},
    }


def test_placement_plan_beats_hash_even_under_zipf():
    """The satellite pin: per-slot traffic shares -> LPT placement must
    carry a lower max-replica load than uniform hash-even when traffic
    is zipf(1.05) — the head slot can no longer wall one replica."""
    from persia_tpu.hotness import placement_plan, slot_weights

    snap = _zipf_snapshot()
    plan = placement_plan(snap, 4, num_slots=64)
    assert plan["max_replica_share"] < plan["hash_even_max_share"]
    # each share is rounded to six places for the report, so the sum
    # may be off by half a unit in the last place per replica
    assert abs(sum(plan["replica_shares"]) - 1.0) <= 4 * 0.5e-6 + 1e-12
    assert len(plan["assignment"]) == 64
    # the weights the plan balanced really concentrate: the head slot
    # outweighs the uniform-share floor
    w = slot_weights(snap, 64)
    assert w.max() > 2.0 * w.sum() / 64
    # and planner_report carries the plan when asked
    from persia_tpu.hotness import planner_report

    rep = planner_report(snap, hbm_bytes=1 << 20, num_replicas=4)
    assert rep["placement_plan"]["num_replicas"] == 4


def test_live_reshard_2_to_3_zero_lost_updates():
    """The tentpole contract end to end, in miniature: real PS services
    over sockets, a trainer thread hammering lookup+update through the
    worker, and a 2→3 hotness-unaware reshard cutting over mid-traffic.
    Afterwards every unit update is accounted for (sum of -row values
    == ships), rows live exactly where the new table routes them, and
    the donor bounced nothing into the void."""
    holders = [_holder() for _ in range(3)]
    services = [_service(h) for h in holders]
    from persia_tpu.service.ps_service import PsClient

    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        _arm(c)
    schema = _schema(n_slots=2)
    table = RoutingTable.uniform(2, slots_per_replica=16)
    worker = EmbeddingWorker(schema, clients[:2], routing=table)
    ships = [0]  # distinct signs shipped with a unit gradient
    ship_lock = threading.Lock()
    stop = threading.Event()
    errors = []

    def train(seed):
        # counting invariant: with unit gradients and summed slots,
        # every sign OCCURRENCE contributes exactly -1 to its row
        # (duplicates within a batch sum their per-sample gradients),
        # so ships counts elements, not distincts
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            feats = [_feature(f"slot_{i}",
                              rng.integers(0, 1 << 24, 128,
                                           dtype=np.uint64))
                     for i in range(2)]
            try:
                ref, out = worker.lookup_direct_training(feats)
                grads = {k: np.ones_like(v.embeddings)
                         for k, v in out.items()}
                worker.update_gradients(ref, grads)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return
            with ship_lock:
                ships[0] += 2 * 128

    threads = [threading.Thread(target=train, args=(s,))
               for s in range(2)]
    for t in threads:
        t.start()
    try:
        controller = ReshardController(clients[:2], table,
                                       workers=[worker],
                                       replay_settle_rows=32)
        import time

        time.sleep(0.5)  # build up live state first
        new_table = controller.reshard_to(3, new_ps_clients=clients)
        assert new_table.num_replicas == 3
        assert worker.routing_epoch == new_table.epoch
        time.sleep(0.5)  # keep training on the new topology
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors[:2]
    controller.finalize(drain_sec=0)
    # --- zero lost updates: every unit gradient is visible as -1 ------
    # count ONLY rows where the new table routes them: donors keep
    # frozen stale copies of moved rows through the double-read window
    # (by design), and those must not double-count
    from tests.fleet_support import owner_filtered_applied

    applied = owner_filtered_applied(holders, new_table, DIM)
    assert abs(applied - ships[0]) < 1e-3, (applied, ships[0])
    # --- rows live where the new table routes them --------------------
    all_signs = []
    for i, h in enumerate(holders):
        signs = [s for shard in h._shards for s in shard._map]
        owners = new_table.replica_of(np.array(signs, np.uint64))
        if i == 2:
            # the newcomer only ever saw new-epoch traffic: it must
            # hold NOTHING it does not own
            assert (owners == 2).all()
        all_signs.extend(s for s, o in zip(signs, owners) if o == i)
    # spot-check served values through the worker (new routing)
    sample = np.array(all_signs[:64], np.uint64)
    rows = worker.lookup_signs(sample, DIM)
    assert (rows <= 0).all()
    worker.close()
    for s in services:
        s.stop()


def test_live_reshard_dance_2_to_4_to_3_zero_lost_updates():
    """Grow then shrink under traffic: two trainer threads hammer
    lookup+update through the worker while the tier goes 2→4→3. The
    counting identity must hold exactly across BOTH cutovers, counted
    over rows at their final owners."""
    from persia_tpu.service.ps_service import PsClient
    from tests.fleet_support import (
        owner_filtered_applied,
        unit_update,
        wait_until,
    )

    holders = [_holder(2_000_000) for _ in range(4)]
    services = [_service(h) for h in holders]
    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        _arm(c)
    table = RoutingTable.uniform(2)
    worker = EmbeddingWorker(_schema(), clients[:2], routing=table)
    bs = 256
    ships = [0]
    ship_lock = threading.Lock()
    stop = threading.Event()
    errors = []

    def train(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            feats = [_feature(f"slot_{i}",
                              rng.integers(0, 1 << 20, bs,
                                           dtype=np.uint64))
                     for i in range(2)]
            try:
                unit_update(worker, feats)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return
            with ship_lock:
                ships[0] += 2 * bs

    def quiet():
        base = ships[0]
        wait_until(lambda: errors or ships[0] >= base + 8 * 2 * bs, 30,
                   "no live traffic between the cutovers")

    threads = [threading.Thread(target=train, args=(s,))
               for s in range(2)]
    for t in threads:
        t.start()
    controller = ReshardController(clients[:2], table, workers=[worker],
                                   replay_settle_rows=64, drain_sec=0.25)
    try:
        quiet()
        t4 = controller.reshard_to(4, new_ps_clients=clients)
        quiet()
        t3 = controller.reshard_to(3)
        quiet()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    try:
        assert not errors, f"trainer thread died mid-reshard: {errors[0]!r}"
        assert not any(t.is_alive() for t in threads), \
            "trainer thread wedged across the reshard"
        controller.finalize(drain_sec=0.0)
        assert (t4.num_replicas, t3.num_replicas) == (4, 3)
        assert worker.routing_epoch == t3.epoch == t4.epoch + 1
        applied = owner_filtered_applied(holders, t3, DIM)
        assert abs(ships[0] - applied) <= 1e-3, (ships[0], applied)
    finally:
        worker.close()
        for s in services:
            s.stop()


@pytest.mark.xfail(strict=True, reason=(
    "ROADMAP Design 14: an update for a row that is no longer there is "
    "skipped, counted in gradient_id_miss_count and acked"))
def test_update_for_a_row_lost_before_it_lands_is_not_acked():
    """A training lookup creates a row in memory only; the replica then
    loses it before the cycle's update arrives (a SIGKILL and a restore
    from a checkpoint that never saw the row; found by the reshard kill
    matrix under load). The trainer must either hear of it or find the
    update applied, and today it gets neither: the miss is a counter."""
    from persia_tpu.service.ps_service import PsClient

    holder = _holder()
    service = _service(holder)
    client = PsClient(service.addr, circuit_breaker=False)
    _arm(client)
    worker = EmbeddingWorker(_schema(n_slots=1), [client])
    signs = np.arange(1, 9, dtype=np.uint64)
    try:
        ref, out = worker.lookup_direct_training([_feature("slot_0", signs)])
        assert len(holder) == len(signs)
        holder.clear()  # the process died; its restore has no such row
        try:
            worker.update_gradients(ref, {
                k: np.ones_like(v.embeddings) for k, v in out.items()})
        except Exception:  # noqa: BLE001 — told: the trainer can retry
            return
        got = -worker.lookup_signs(signs, DIM).sum(axis=1) / DIM
        assert (got == 1).all(), (
            f"acked and not applied: {got} "
            f"(gradient_id_miss_count={holder.gradient_id_miss_count})")
    finally:
        worker.close()
        service.stop()


def test_hotness_balanced_table_serves_lower_max_share_than_hash_even():
    """The placement plan, measured and not only planned: zipf(1.05)
    traffic with a hot set in every batch goes through a 4-replica
    fleet under uniform hash-even routing and under the table planned
    from the fleet's OWN merged sketches. Load is counted server-side
    (per-replica hotness totals = signs actually served); the balanced
    table's busiest replica must serve a smaller share."""
    from persia_tpu import hotness, knobs
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service.ps_service import PsClient

    services = [_service(EmbeddingHolder(capacity=2_000_000, hotness=True))
                for _ in range(4)]
    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        _arm(c)
    spr = int(knobs.get("PERSIA_ROUTING_SLOTS_PER_REPLICA"))
    even = RoutingTable(1, np.arange(4 * spr, dtype=np.int32) % 4, 4)
    worker = EmbeddingWorker(_schema(), clients, routing=even)
    rng = np.random.default_rng(11)
    # the serving tier dedups per batch, so slot-level skew comes from
    # hot signs CLUSTERING on slots: ~128 hot signs over 256 slots
    # hands some replica 2-3x its fair share under hash-even
    hot_p = np.arange(1, 129, dtype=np.float64) ** -1.05
    hot_p /= hot_p.sum()
    with np.errstate(over="ignore"):
        hot_pool = (np.arange(1, 129, dtype=np.uint64)
                    * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(1)

    def zipf_feats(bs=256):
        n_hot = int(bs * 0.7)
        signs = np.concatenate([
            rng.choice(hot_pool, size=n_hot, p=hot_p),
            rng.integers(1 << 30, 1 << 40, bs - n_hot, dtype=np.uint64)])
        return [_feature(f"slot_{i}", signs) for i in range(2)]

    try:
        for _ in range(12):  # sketch-building pass
            worker.lookup_direct(zipf_feats(), training=False)
        plan = hotness.placement_plan(
            hotness.merge_snapshots([c.hotness() for c in clients]), 4,
            current_table=even)
        balanced = even.derive(np.asarray(plan["assignment"], np.int32), 4,
                               weights=np.asarray(plan["slot_weights"]))
        trace = [zipf_feats() for _ in range(24)]

        def max_served_share(tbl):
            worker.apply_routing(tbl)
            worker.close_routing_window()
            before = [c.hotness().get("total", 0) for c in clients]
            for feats in trace:
                worker.lookup_direct(feats, training=False)
            served = np.array([c.hotness().get("total", 0)
                               for c in clients], np.float64) - before
            return float((served / max(served.sum(), 1.0)).max())

        even_max = max_served_share(even.derive(even.replica_of_slot, 4))
        balanced_max = max_served_share(
            balanced.derive(balanced.replica_of_slot, 4))
        assert balanced_max < even_max, (balanced_max, even_max)
    finally:
        worker.close()
        for s in services:
            s.stop()


def test_freeze_bounces_writes_with_typed_stale_error():
    """Donor-side cutover protocol, deterministically: after freeze,
    training lookups and updates touching a moving slot bounce with
    the routing_stale error (epoch attached); eval reads keep serving
    (double-read); untouched slots are unaffected; finish re-opens."""
    holder = _holder()
    svc = _service(holder)
    from persia_tpu.rpc import RpcError
    from persia_tpu.service.ps_service import PsClient

    client = PsClient(svc.addr, circuit_breaker=False)
    _arm(client)
    t = RoutingTable.uniform(1, slots_per_replica=8)
    signs = np.arange(512, dtype=np.uint64)
    client.lookup(signs, DIM, True)  # create rows
    moving = [0, 3]
    slot_of = t.slot_of(signs)
    moving_signs = signs[np.isin(slot_of, moving)]
    still_signs = signs[~np.isin(slot_of, moving)]
    n = client.reshard_begin(moving, t.num_slots, epoch=2)
    assert n == len(moving_signs)
    # captured writes during the copy window replay with CURRENT state
    client.update_gradients(moving_signs[:4],
                            np.ones((4, DIM), np.float32), DIM)
    drained = unpack_rows(client.reshard_drain())
    assert {s for s, _d, _v in drained} == set(
        int(x) for x in moving_signs[:4])
    assert all(v[0] == -1.0 for _s, _d, v in drained)
    client.reshard_freeze(epoch=2)
    with pytest.raises(RpcError) as ei:
        client.update_gradients(moving_signs[:4],
                                np.ones((4, DIM), np.float32), DIM)
    assert is_routing_stale(ei.value) == 2
    with pytest.raises(RpcError):
        client.lookup(moving_signs[:2], DIM, True)
    # eval reads still serve, and untouched slots take writes
    assert client.lookup(moving_signs[:2], DIM, False).shape == (2, DIM)
    client.update_gradients(still_signs[:4],
                            np.ones((4, DIM), np.float32), DIM)
    fin = client.reshard_finish()
    assert fin["was_active"]
    client.update_gradients(moving_signs[:4],
                            np.ones((4, DIM), np.float32), DIM)
    svc.stop()


def test_inc_replay_filters_through_new_routing_table(tmp_path):
    """Satellite regression: packets dumped by a 2-replica fleet replay
    onto a 3-replica fleet with per-sign OWNERSHIP filtering — each
    recovered replica reconstructs exactly the rows the NEW table
    routes to it, never a row it no longer owns (2→3 replay)."""
    from persia_tpu.inc_update import (
        IncrementalUpdateDumper,
        IncrementalUpdateLoader,
    )

    inc_dir = str(tmp_path / "inc")
    old = RoutingTable.uniform(2)
    rng = np.random.default_rng(3)
    signs = rng.integers(0, 1 << 40, 600, dtype=np.uint64)
    signs = np.unique(signs)
    owners_old = old.replica_of(signs)
    # two old-fleet replicas dump their rows as inc packets
    for r in (0, 1):
        h = _holder()
        mine = signs[owners_old == r]
        for s in mine:
            h.set_entry(int(s), DIM,
                        np.full(2 * DIM, float(int(s) % 97), np.float32))
        d = IncrementalUpdateDumper(h, inc_dir, buffer_size=10**9,
                                    replica_index=r)
        d.commit(mine)
        d.flush()
    new = RoutingTable.uniform(3)
    recovered = []
    for r in range(3):
        h = _holder()
        loaded = IncrementalUpdateLoader(
            h, inc_dir, replica_index=r, routing=new).scan_once()
        got = {s for shard in h._shards for s in shard._map}
        want = {int(s) for s in signs[new.replica_of(signs) == r]}
        assert got == want, f"replica {r}: ownership filter broken"
        assert loaded == len(want)
        recovered.append(got)
    # partition: no loss, no overlap across the recovered fleet
    assert set().union(*recovered) == {int(s) for s in signs}
    assert sum(len(g) for g in recovered) == len(signs)
    # the legacy filename filter (no routing) would have loaded NOTHING
    # for the new replica index 2 — the regression this pins
    h = _holder()
    assert IncrementalUpdateLoader(
        h, inc_dir, replica_index=2).scan_once() == 0


def test_checkpoint_dump_uniform_is_bit_identical(tmp_path):
    """fp32 checkpoints under a uniform table stay PSD v1 bit-identical
    to the pre-routing stack (marker included)."""
    import filecmp

    from persia_tpu.checkpoint import dump_sharded, load_sharded

    holders = [_holder() for _ in range(2)]
    t = RoutingTable.uniform(2)
    rng = np.random.default_rng(4)
    signs = np.unique(rng.integers(0, 1 << 40, 300, dtype=np.uint64))
    for s, owner in zip(signs, t.replica_of(signs)):
        holders[owner].set_entry(int(s), DIM,
                                 np.full(2 * DIM, 1.5, np.float32))
    d_legacy, d_routed = str(tmp_path / "a"), str(tmp_path / "b")
    dump_sharded(holders, d_legacy)  # legacy call shape
    dump_sharded(holders, d_routed, routing=t)
    for name in sorted(os.listdir(d_legacy)):
        assert filecmp.cmp(os.path.join(d_legacy, name),
                           os.path.join(d_routed, name),
                           shallow=False), f"{name} differs"
    # and a NON-uniform table records itself + loads correctly
    custom = t.derive((t.replica_of_slot + 1) % 2, 2)
    d_custom = str(tmp_path / "c")
    dump_sharded(holders, d_custom, routing=custom)
    import json

    marker = json.load(open(os.path.join(d_custom,
                                         "embedding_dump_done")))
    assert marker["routing"]["epoch"] == custom.epoch
    fresh = [_holder() for _ in range(2)]
    load_sharded(fresh, d_legacy, routing=custom)
    for h, owner in zip(fresh, range(2)):
        got = {s for shard in h._shards for s in shard._map}
        want = {int(s) for s in signs
                if int(custom.replica_of(np.array([s], np.uint64))[0])
                == owner}
        assert got == want


def test_operator_scale_sequences_reshard_around_pods():
    """Scale-out creates PS pods BEFORE the migration runs onto them;
    scale-in drains slots off dying replicas BEFORE their pods go;
    driverless scale-in refuses to delete pods (pending_drain)."""
    from persia_tpu.k8s_operator import FakeKubeApi, Operator

    spec = {"jobName": "j", "image": "persia:latest",
            "embeddingConfigPath": "/config/embedding_config.yml",
            "roles": {"embeddingParameterServer": {"replicas": 2},
                      "embeddingWorker": {"replicas": 1}}}

    def ps_pods(api):
        return sorted(o["metadata"]["name"]
                      for o in api.list_objects("persia-job=j")
                      if o["kind"] == "Pod"
                      and "parameterserver" in o["metadata"]["name"])

    calls = []

    api = FakeKubeApi()

    def driver(job, old, new, phase, drv_spec):
        calls.append((job, old, new, phase, len(ps_pods(api))))

    op = Operator(api, [dict(spec, roles={
        k: dict(v) for k, v in spec["roles"].items()})],
        reshard_driver=driver)
    op.reconcile_all()
    assert len(ps_pods(api)) == 2
    ev = op.scale_ps("j", 4)
    assert ev["status"] == "done"
    # driver saw the GROWN pod set (pods first, then migrate onto them)
    assert calls[-1] == ("j", 2, 4, "scale_out", 4)
    assert len(ps_pods(api)) == 4
    ev = op.scale_ps("j", 3)
    # driver ran while the dying pod still existed (drain before delete)
    assert calls[-1] == ("j", 4, 3, "scale_in", 4)
    assert len(ps_pods(api)) == 3
    assert [e["status"] for e in op.reshard_events()] == ["done", "done"]
    # driverless operator records the intent but keeps the pods
    op2 = Operator(FakeKubeApi(), [dict(spec, roles={
        k: dict(v) for k, v in spec["roles"].items()})])
    op2.reconcile_all()
    ev = op2.scale_ps("j", 1)
    assert ev["status"] == "pending_drain"
    assert len(ps_pods(op2.api)) == 2  # nothing deleted


# --- crash safety: journal, fencing, lease, resume --------------------------


def test_migration_journal_records_and_state(tmp_path):
    j = MigrationJournal(str(tmp_path / "jr"))
    assert j.state() is None
    t = RoutingTable.uniform(2, slots_per_replica=4)
    t2 = t.derive((t.replica_of_slot + 1) % 2, 2)
    j.append("plan", mig_id="m1", attempt=0, epoch=t2.epoch,
             old_table=t.to_doc(), new_table=t2.to_doc(),
             moves=[{"donor": 0, "target": 1, "slots": [0]}])
    j.append("copy_done", mig_id="m1", attempt=0, donor=0)
    st = j.state()
    assert st["phase"] == "copying" and st["copied"] == [0]
    j.append("frozen", mig_id="m1", attempt=0, donor=0, slots=[0])
    j.append("publish_start", mig_id="m1", attempt=0, epoch=t2.epoch)
    assert j.state()["phase"] == "publishing"
    j.append("published", mig_id="m1", attempt=0, epoch=t2.epoch)
    assert j.state()["phase"] == "published"
    j.append("finalized", mig_id="m1", attempt=0)
    st = j.state()
    assert st["phase"] == "finalized"
    # a second journal over the same dir resumes the seq counter and
    # replays identically (the restart path)
    j2 = MigrationJournal(str(tmp_path / "jr"))
    assert j2.state() == st
    rec = j2.append("plan", mig_id="m2", attempt=0, epoch=t2.epoch + 1,
                    old_table=t2.to_doc(), new_table=t2.to_doc(),
                    moves=[])
    assert rec["seq"] > 6
    assert j2.state()["mig_id"] == "m2"
    # a torn write (leftover .tmp) is invisible
    open(str(tmp_path / "jr" / "rec_000099_plan.json.tmp"), "w").close()
    assert j2.state()["mig_id"] == "m2"
    # zombie fencing: a superseded attempt's straggler records (a
    # fenced-out controller still journals its rollback) must not
    # poison the live attempt's state
    j2.append("resume", mig_id="m2", attempt=1, from_phase="planned")
    j2.append("plan", mig_id="m2", attempt=1, epoch=t2.epoch + 1,
              old_table=t2.to_doc(), new_table=t2.to_doc(), moves=[])
    j2.append("published", mig_id="m2", attempt=1, epoch=t2.epoch + 1)
    j2.append("aborted", mig_id="m2", attempt=0)  # zombie's rollback
    st = j2.state()
    assert st["phase"] == "published" and st["attempt"] == 1


def _drive_subprocess(journal, addrs, table, to, die_at=None,
                      env_extra=None):
    """Run the migration controller as a real subprocess (the chaos
    harness's controller actor); returns the completed process."""
    os.makedirs(journal, exist_ok=True)
    table_path = os.path.join(journal, "current_table.json")
    with open(table_path, "w") as f:
        json.dump(table.to_doc(), f)
    cmd = [sys.executable, "-m", "persia_tpu.reshard",
           "--journal", journal, "--ps", ",".join(addrs),
           "--table", table_path, "--to", str(to)]
    if die_at:
        cmd += ["--die-at", die_at]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(cmd, env=env, capture_output=True, timeout=120)


@pytest.mark.parametrize("die_at,expect_action", [
    ("freeze", "resumed"),        # pre-publish: fence out + re-execute
    ("drain", "republished"),     # post-publish: roll forward
])
def test_controller_killed_mid_migration_resumes_from_journal(
        tmp_path, die_at, expect_action):
    """The tentpole acceptance pin: a REAL controller process SIGKILLs
    itself (faults `die` at the reshard.controller site) at a protocol
    state, and a fresh controller resumes the SAME migration from the
    durable journal — completing it, disarming every donor, and
    preserving the counting identity."""
    holders = [_holder() for _ in range(3)]
    services = [_service(h) for h in holders]
    from persia_tpu.service.ps_service import PsClient

    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        _arm(c)
    table = RoutingTable.uniform(2, slots_per_replica=8)
    worker = EmbeddingWorker(schema=_schema(2), ps_clients=clients[:2],
                             routing=table)
    journal = str(tmp_path / "journal")
    rng = np.random.default_rng(5)
    signs = rng.integers(0, 1 << 30, 2048, dtype=np.uint64)
    ships = 0
    feats = [_feature(f"slot_{i}", signs[i * 1024:(i + 1) * 1024])
             for i in range(2)]
    ref, out = worker.lookup_direct_training(feats)
    worker.update_gradients(ref, {k: np.ones_like(v.embeddings)
                                  for k, v in out.items()})
    ships += 2 * 1024

    proc = _drive_subprocess(journal, [c.addr for c in clients], table,
                             to=3, die_at=die_at)
    assert proc.returncode != 0, "driver should have died mid-migration"
    st = MigrationJournal(journal).state()
    assert st is not None and st["phase"] not in ("finalized", "aborted")

    ctrl, action = ReshardController.resume(journal, clients,
                                            workers=[worker])
    assert action == expect_action
    ctrl.finalize(drain_sec=0)
    new_table = ctrl.table
    assert new_table.epoch == table.epoch + 1
    assert new_table.num_replicas == 3
    assert worker.routing_epoch == new_table.epoch
    assert MigrationJournal(journal).state()["phase"] == "finalized"
    # every donor disarmed (no frozen-forever shard)
    for c in clients:
        assert c.reshard_status()["active"] is False
    # counting identity at the new owners: no update lost across the
    # kill + resume
    applied = 0.0
    for i, h in enumerate(holders):
        rows = [(s, -float(vec[:d].sum()) / DIM)
                for shard in h._shards
                for s, (d, vec) in shard._map.items()]
        if not rows:
            continue
        owners = new_table.replica_of(
            np.array([s for s, _ in rows], np.uint64))
        applied += sum(v for (_s, v), o in zip(rows, owners) if o == i)
    assert abs(applied - ships) < 1e-3, (applied, ships)
    # and training continues on the new topology
    ref, out = worker.lookup_direct_training(feats)
    worker.update_gradients(ref, {k: np.ones_like(v.embeddings)
                                  for k, v in out.items()})
    worker.close()
    for s in services:
        s.stop()


def test_resume_noop_on_terminal_journal(tmp_path):
    holders = [_holder() for _ in range(2)]
    services = [_service(h) for h in holders]
    from persia_tpu.service.ps_service import PsClient

    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        _arm(c)
    table = RoutingTable.uniform(2, slots_per_replica=4)
    worker = EmbeddingWorker(schema=_schema(2), ps_clients=clients,
                             routing=table)
    journal = str(tmp_path / "jr")
    ctrl = ReshardController(clients, table, workers=[worker],
                             journal_dir=journal)
    new = ctrl.execute(table.derive(
        (table.replica_of_slot + 1) % 2, 2))
    ctrl.finalize(drain_sec=0)
    ctrl2, action = ReshardController.resume(journal, clients,
                                             workers=[worker])
    assert action == "noop"
    assert ctrl2.table == new
    with pytest.raises(Exception):
        ReshardController.resume(str(journal) + "_empty", clients)
    worker.close()
    for s in services:
        s.stop()


def test_fencing_rejects_superseded_controller():
    """Fenced stale-controller calls arriving after a newer migration
    began must be rejected — finish most critically (a late disarm
    from a dead attempt would drop the live attempt's capture set)."""
    holder = _holder()
    svc = _service(holder)
    from persia_tpu.rpc import RpcError
    from persia_tpu.service.ps_service import PsClient

    client = PsClient(svc.addr, circuit_breaker=False)
    _arm(client)
    t = RoutingTable.uniform(1, slots_per_replica=8)
    client.lookup(np.arange(64, dtype=np.uint64), DIM, True)
    # attempt (2, 0) arms; newer attempt (2, 1) takes over
    client.reshard_begin([0], t.num_slots, epoch=2, fence=(2, 0),
                         mig_id="mA")
    client.reshard_begin([0], t.num_slots, epoch=2, fence=(2, 1),
                         mig_id="mA")
    st = client.reshard_status()
    assert st["token"] == [2, 1]
    # every verb of the superseded attempt bounces with the typed error
    for call in (
        lambda: client.reshard_finish(fence=(2, 0), mig_id="mA"),
        lambda: client.reshard_freeze(epoch=2, fence=(2, 0)),
        lambda: client.reshard_drain(fence=(2, 0)),
        lambda: client.reshard_extract(16, fence=(2, 0)),
        lambda: client.reshard_begin([0], t.num_slots, epoch=2,
                                     fence=(2, 0), mig_id="mA"),
        lambda: client.reshard_install(pack_rows([]), fence=(2, 0)),
    ):
        with pytest.raises(RpcError) as ei:
            call()
        assert is_reshard_fenced(ei.value) == (2, 1), ei.value
    # the live attempt is untouched and still disarmable
    assert client.reshard_status()["active"] is True
    fin = client.reshard_finish(fence=(2, 1), mig_id="mA")
    assert fin["was_active"] is True
    # a NEWER epoch's migration (3, 0) fences out everything from 2
    client.reshard_begin([1], t.num_slots, epoch=3, fence=(3, 0),
                         mig_id="mB")
    with pytest.raises(RpcError) as ei:
        client.reshard_finish(fence=(2, 1))
    assert is_reshard_fenced(ei.value) == (3, 0)
    client.reshard_finish(fence=(3, 0))
    svc.stop()


def test_reshard_retries_are_idempotent():
    """Retry-after-ambiguous-timeout safety: repeated begin (same
    token) re-arms, repeated freeze is a no-op, repeated install
    converges to the same rows, repeated finish answers
    was_active=False."""
    holder = _holder()
    svc = _service(holder)
    from persia_tpu.service.ps_service import PsClient

    client = PsClient(svc.addr, circuit_breaker=False)
    _arm(client)
    t = RoutingTable.uniform(1, slots_per_replica=8)
    signs = np.arange(256, dtype=np.uint64)
    client.lookup(signs, DIM, True)
    n1 = client.reshard_begin([0, 1], t.num_slots, epoch=2,
                              fence=(2, 0), mig_id="m")
    n2 = client.reshard_begin([0, 1], t.num_slots, epoch=2,
                              fence=(2, 0), mig_id="m")
    assert n1 == n2  # re-arm re-snapshots the same moving rows
    client.reshard_freeze(epoch=2, fence=(2, 0))
    client.reshard_freeze(epoch=2, fence=(2, 0))  # no-op, no error
    assert client.reshard_status()["frozen"] is True
    rows = [(int(s), DIM, np.full(2 * DIM, -3.0, np.float32))
            for s in signs[:4]]
    assert client.reshard_install(pack_rows(rows), fence=(2, 0)) == 4
    assert client.reshard_install(pack_rows(rows), fence=(2, 0)) == 4
    got = holder.get_entry(int(signs[0]))
    np.testing.assert_array_equal(got[1], rows[0][2])
    assert client.reshard_finish(fence=(2, 0))["was_active"] is True
    assert client.reshard_finish(fence=(2, 0))["was_active"] is False
    svc.stop()


def test_freeze_lease_auto_thaws_dead_controllers_donor(monkeypatch):
    """Donor self-healing: a controller that freezes and then vanishes
    must not leave a frozen-forever shard — the lease expires, the
    donor discards capture and serves the OLD epoch again, and the
    metrics record the thaw."""
    holder = _holder()
    svc = _service(holder)
    from persia_tpu.rpc import RpcError
    from persia_tpu.service.ps_service import PsClient

    client = PsClient(svc.addr, circuit_breaker=False)
    _arm(client)
    t = RoutingTable.uniform(1, slots_per_replica=4)
    signs = np.arange(128, dtype=np.uint64)
    client.lookup(signs, DIM, True)
    moving = [int(s) for s in np.unique(t.slot_of(signs))]  # all slots
    client.reshard_begin(moving, t.num_slots, epoch=2, fence=(2, 0),
                         mig_id="m", lease_sec=0.4)
    client.reshard_freeze(epoch=2, fence=(2, 0))
    with pytest.raises(RpcError) as ei:
        client.update_gradients(signs[:8], np.ones((8, DIM), np.float32),
                                DIM)
    assert is_routing_stale(ei.value) == 2
    before = svc._c_lease_expired.value
    deadline = time.monotonic() + 5.0
    # no heartbeat arrives; the guard on the next write (the bounced
    # writer's retry) trips the expiry
    while time.monotonic() < deadline:
        try:
            client.update_gradients(signs[:8],
                                    np.ones((8, DIM), np.float32), DIM)
            break
        except RpcError:
            time.sleep(0.05)
    else:
        pytest.fail("donor never auto-thawed within 5s of lease expiry")
    st = client.reshard_status()
    assert st["active"] is False
    assert svc._c_lease_expired.value == before + 1
    # the dead controller's stragglers stay fenced out even after thaw
    with pytest.raises(RpcError) as ei:
        client.reshard_drain(fence=(1, 9))
    assert is_reshard_fenced(ei.value) == (2, 0)
    # ...and a resumed attempt (higher token) can re-begin
    assert client.reshard_begin(moving, t.num_slots, epoch=2,
                                fence=(2, 1), mig_id="m",
                                lease_sec=30.0) >= 0
    client.reshard_finish(fence=(2, 1))
    svc.stop()


def test_reshard_rpc_deadline_bounds_wedged_donor(monkeypatch):
    """The __deadline__ satellite: once the controller arms
    PERSIA_RESHARD_RPC_TIMEOUT_SEC, a wedged replica sheds the expired
    reshard RPC (typed RpcDeadlineExceeded) instead of hanging the
    migration; the knob off (0) keeps the legacy unbounded behavior
    and an unarmed client never negotiates the probe."""
    from persia_tpu import faults
    from persia_tpu.rpc import RpcDeadlineExceeded
    from persia_tpu.service.ps_service import PsClient, PsService

    holder = _holder()
    # serial dispatch: the injected recv delay must land AFTER the
    # deadline slot is parsed for the shed check to see it expired
    svc = PsService(holder, port=0, concurrent_streams=1)
    svc.server.serve_background()
    client = PsClient(svc.addr, circuit_breaker=False)
    assert client.client.enable_deadline is False  # idle wire: no probe
    monkeypatch.setenv("PERSIA_RESHARD_RPC_TIMEOUT_SEC", "0.05")
    client.enable_reshard_deadline()
    assert client.client.enable_deadline is True
    try:
        faults.add("rpc.server.recv", "delay", arg=0.25,
                   method="reshard_status")
        with pytest.raises(RpcDeadlineExceeded):
            client.reshard_status(fence=(1, 0))
    finally:
        faults.reset_faults()
    # non-reshard calls stay deadline-free (no default deadline)
    assert client.lookup(np.arange(4, dtype=np.uint64), DIM,
                         False).shape == (4, DIM)
    svc.stop()


# --- routing-edge races ------------------------------------------------------


def test_double_epoch_bounce_settles_on_skipped_epoch():
    """A writer bounced with min_epoch=N must settle when the fleet
    publishes N+1 directly (two derive()s while it waited) — the wait
    condition is >=, never ==."""
    holders = [_holder() for _ in range(2)]
    services = [_service(h) for h in holders]
    from persia_tpu.service.ps_service import PsClient

    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        _arm(c)
    t1 = RoutingTable.uniform(1, slots_per_replica=8)
    worker = EmbeddingWorker(schema=_schema(1), ps_clients=clients[:1],
                             routing=t1)
    signs = np.arange(512, dtype=np.uint64)
    feats = [_feature("slot_0", signs)]
    ref, out = worker.lookup_direct_training(feats)
    # freeze EVERY slot on donor 0 demanding epoch 2
    clients[0].reshard_begin(list(range(t1.num_slots)), t1.num_slots,
                             epoch=2, fence=(2, 0), mig_id="m")
    # copy all rows over to replica 1 so the post-swap writes land on
    # a replica that owns them
    rows = []
    for shard in holders[0]._shards:
        for s, (d, vec) in list(shard._map.items()):
            rows.append((int(s), d, vec.copy()))
    clients[1].reshard_install(pack_rows(rows), fence=(2, 0))
    clients[0].reshard_freeze(epoch=2, fence=(2, 0))

    t2 = t1.derive(t1.replica_of_slot, 1)                    # epoch 2
    t3 = t2.derive(np.ones(t1.num_slots, np.int32) * 0 + 1, 2)  # epoch 3

    def publish_skipping():
        time.sleep(0.3)
        # the fleet jumps straight to epoch 3 (slots -> replica 1)
        worker.apply_routing(t3, ps_clients=clients)
        clients[0].reshard_finish(fence=(2, 0))

    pub = threading.Thread(target=publish_skipping)
    pub.start()
    # bounced update: demands epoch 2, must settle under epoch 3
    worker.update_gradients(ref, {"slot_0": np.ones(
        (len(signs), DIM), np.float32)})
    pub.join(timeout=10)
    assert worker.routing_epoch == 3
    # the update landed exactly once, on the NEW owner
    applied = -sum(float(vec[:d].sum()) / DIM
                   for shard in holders[1]._shards
                   for _s, (d, vec) in shard._map.items())
    assert abs(applied - len(signs)) < 1e-3, applied
    worker.close()
    for s in services:
        s.stop()


def test_gradient_return_across_epoch_resplits_by_live_table():
    """A reshard cutting over between a batch's forward and its
    gradient return must not ship by the cached forward split — the
    moved signs would land on a donor whose capture already disarmed
    and read back as lost updates (the chaos matrix's donor:cutover
    forensic). The update path detects the epoch crossing and
    re-splits by the live table."""
    holders = [_holder() for _ in range(3)]
    services = [_service(h) for h in holders]
    from persia_tpu.service.ps_service import PsClient

    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        _arm(c)
    t2 = RoutingTable.uniform(2, slots_per_replica=8)
    worker = EmbeddingWorker(schema=_schema(2), ps_clients=clients[:2],
                             routing=t2)
    signs = np.arange(1024, dtype=np.uint64)
    feats = [_feature(f"slot_{i}", signs[i * 512:(i + 1) * 512])
             for i in range(2)]
    ref, out = worker.lookup_direct_training(feats)  # split at epoch 1
    # cutover lands mid-pipeline: move every slot to replica 2, and
    # copy the rows over so the re-split update finds them there
    rows = []
    for h in holders[:2]:
        for shard in h._shards:
            for s, (d, vec) in list(shard._map.items()):
                rows.append((int(s), d, vec.copy()))
    clients[2].reshard_install(pack_rows(rows))
    t3 = t2.derive(np.full(t2.num_slots, 2, np.int32), 3)
    assert worker.apply_routing(t3, ps_clients=clients)
    worker.update_gradients(ref, {k: np.ones_like(v.embeddings)
                                  for k, v in out.items()})
    # every update landed on the LIVE owner (replica 2), none on the
    # disarmed donors' stale copies
    applied_target = -sum(float(vec[:d].sum()) / DIM
                          for shard in holders[2]._shards
                          for _s, (d, vec) in shard._map.items())
    assert abs(applied_target - 1024) < 1e-3, applied_target
    for h in holders[:2]:
        stale = -sum(float(vec[:d].sum()) / DIM
                     for shard in h._shards
                     for _s, (d, vec) in shard._map.items())
        assert abs(stale) < 1e-3, stale
    worker.close()
    for s in services:
        s.stop()


def test_update_retried_after_a_cutover_resplits_by_live_table(
        monkeypatch):
    """An update whose first shipment dies with its replica and whose
    settle loop runs out of budget (one call's retry ladder against a
    dead address can outlast it) comes back through the whole-fan-out
    retry. If a migration cut over and finalized meanwhile, the groups
    split before it must not ship: the donor is disarmed and would take
    the moved signs onto copies nobody reads, acked (the reshard kill
    matrix's donor cells, about one kill in thirteen). The retry
    re-splits by the live table."""
    holders = [_holder() for _ in range(3)]
    services = [_service(h) for h in holders]
    from persia_tpu.service.ps_service import PsClient

    clients = [PsClient(s.addr, circuit_breaker=False) for s in services]
    for c in clients:
        _arm(c)
    t2 = RoutingTable.uniform(2, slots_per_replica=8)
    worker = EmbeddingWorker(schema=_schema(2), ps_clients=clients[:2],
                             routing=t2)
    signs = np.arange(1024, dtype=np.uint64)
    feats = [_feature(f"slot_{i}", signs[i * 512:(i + 1) * 512])
             for i in range(2)]
    ref, out = worker.lookup_direct_training(feats)
    t3 = t2.derive(np.full(t2.num_slots, 2, np.int32), 3)

    class DiesOnceWhileTheFleetMovesOn:
        """Replica 0's client: its first update finds the process gone,
        and by the time the call gives up every slot has moved to
        replica 2 and the donors are disarmed."""

        def __init__(self, client):
            self._client = client
            self.died = False

        def __getattr__(self, name):
            return getattr(self._client, name)

        def update_gradients(self, *a, **kw):
            if self.died:
                return self._client.update_gradients(*a, **kw)
            self.died = True
            rows = [(int(s), d, vec.copy())
                    for h in holders[:2] for shard in h._shards
                    for s, (d, vec) in list(shard._map.items())]
            clients[2].reshard_install(pack_rows(rows))
            assert worker.apply_routing(t3, ps_clients=clients)
            raise ConnectionError("replica 0 is gone")

    worker.ps_clients[0] = DiesOnceWhileTheFleetMovesOn(clients[0])
    monkeypatch.setenv("PERSIA_RESHARD_STALE_RETRY_SEC", "0")
    try:
        worker.update_gradients(ref, {k: np.ones_like(v.embeddings)
                                      for k, v in out.items()})
        # every sign reached the live owner (replica 1's half perhaps
        # twice: once copied with its rows, once re-shipped, as any
        # whole-fan-out retry is at-least-once), and replica 0's
        # unreachable copies took nothing
        got = -worker.lookup_signs(signs, DIM).sum(axis=1) / DIM
        assert ((got >= 1) & (got <= 2)).all(), got
        assert not any(vec[:d].any() for shard in holders[0]._shards
                       for d, vec in shard._map.values())
    finally:
        worker.close()
        for s in services:
            s.stop()


def test_routing_holder_swap_under_reader_load():
    """RoutingHolder hammer: concurrent table/prev reads, applies, and
    window closes must never tear (prev must always be a table or None,
    epochs monotone from the readers' view)."""
    from persia_tpu.routing import RoutingHolder

    t = RoutingTable.uniform(2, slots_per_replica=8)
    holder = RoutingHolder(t)
    stop = threading.Event()
    errors = []

    def reader():
        last = 0
        while not stop.is_set():
            try:
                tab = holder.table
                assert tab.epoch >= last
                last = tab.epoch
                prev = holder.prev
                if prev is not None:
                    # (no ordering claim vs `tab`: two swaps may land
                    # between the two unsynchronized reads)
                    assert prev.num_slots == tab.num_slots
                    assert prev.epoch < holder.table.epoch
                _ = tab.replica_of(np.arange(16, dtype=np.uint64))
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    def closer():
        while not stop.is_set():
            holder.close_window()
            time.sleep(0.001)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    threads.append(threading.Thread(target=closer))
    for th in threads:
        th.start()
    cur = t
    rng = np.random.default_rng(0)
    for _ in range(200):
        cur = cur.derive(
            rng.integers(0, 2, cur.num_slots).astype(np.int32), 2)
        assert holder.apply(cur)
        # duplicate + stale publishes are no-ops
        assert holder.apply(cur) is False
        assert holder.apply(t) is False
    stop.set()
    for th in threads:
        th.join(timeout=10)
    assert not errors, errors[:2]
    assert holder.epoch == cur.epoch


def test_operator_resumes_journaled_migration_on_restart(tmp_path):
    """Operator-crash recovery: a restarted operator's first reconcile
    scans the per-job migration journals and hands in-flight ones to
    the driver under phase 'resume' (or records resume_pending without
    a driver)."""
    from persia_tpu.k8s_operator import FakeKubeApi, Operator

    spec = {"jobName": "j", "image": "persia:latest",
            "embeddingConfigPath": "/config/embedding_config.yml",
            "roles": {"embeddingParameterServer": {"replicas": 2},
                      "embeddingWorker": {"replicas": 1}}}
    jdir = str(tmp_path / "journals")
    t = RoutingTable.uniform(2, slots_per_replica=4)
    t2 = t.derive(np.zeros(t.num_slots, np.int32), 1)
    j = MigrationJournal(os.path.join(jdir, "j"))
    j.append("plan", mig_id="m1", attempt=0, epoch=t2.epoch,
             old_table=t.to_doc(), new_table=t2.to_doc(),
             moves=[{"donor": 1, "target": 0, "slots": [1]}])
    j.append("frozen", mig_id="m1", attempt=0, donor=1, slots=[1])

    calls = []
    op = Operator(FakeKubeApi(), [dict(spec, roles={
        k: dict(v) for k, v in spec["roles"].items()})],
        reshard_driver=lambda *a: calls.append(a),
        reshard_journal_dir=jdir)
    op.reconcile_all()
    assert calls and calls[0][3] == "resume" and calls[0][2] == 1
    assert op.reshard_events()[0]["status"] == "resumed"
    # second pass does not re-fire the scan
    op.reconcile_all()
    assert len(calls) == 1
    # driverless operator surfaces the wedged migration instead
    op2 = Operator(FakeKubeApi(), [dict(spec, roles={
        k: dict(v) for k, v in spec["roles"].items()})],
        reshard_journal_dir=jdir)
    op2.reconcile_all()
    assert op2.reshard_events()[0]["status"] == "resume_pending"
    # a finalized journal is quiet
    j.append("finalized", mig_id="m1", attempt=0)
    op3 = Operator(FakeKubeApi(), [dict(spec, roles={
        k: dict(v) for k, v in spec["roles"].items()})],
        reshard_journal_dir=jdir)
    op3.reconcile_all()
    assert op3.reshard_events() == []
