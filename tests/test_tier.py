"""Hierarchical embedding tier ladder: PersiaPath spill round trips,
the SpillStore's packet/index/budget semantics, holder fault-in parity,
the hotness-admitted device-cache mapper, and the set_entries coherence
protocol (version stream + inc-update log + the wv rider)."""

import os

import numpy as np
import optax
import pytest

from persia_tpu.ps.spill import SpillReadError, SpillStore
from persia_tpu.ps.store import EmbeddingHolder
from persia_tpu.storage import PersiaPath
from persia_tpu.worker.device_cache import SignSlotMap, TieredSignSlotMap

DIM = 8


def _armed_holder(capacity=64, shards=4, spill_dir=None, **kw):
    h = EmbeddingHolder(capacity=capacity, num_internal_shards=shards,
                        spill_dir=spill_dir, **kw)
    h.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
    h.register_optimizer({"type": "adagrad", "lr": 0.1,
                          "initialization": 0.01,
                          "g_square_momentum": 1.0,
                          "vectorwise_shared": False})
    return h


# --- storage.PersiaPath primitives ---------------------------------------


def test_persia_path_read_range(tmp_path):
    p = PersiaPath(str(tmp_path / "blob"))
    p.write_bytes(bytes(range(100)))
    assert p.read_range(0, 10) == bytes(range(10))
    assert p.read_range(90, 10) == bytes(range(90, 100))
    with pytest.raises(IOError):
        p.read_range(95, 10)  # short read must raise, not truncate


def test_persia_path_write_bytes_atomic(tmp_path):
    p = PersiaPath(str(tmp_path / "pkt"))
    p.write_bytes_atomic(b"first")
    assert p.read_bytes() == b"first"
    p.write_bytes_atomic(b"second-longer")
    assert p.read_bytes() == b"second-longer"
    # no .tmp debris after a successful atomic write
    assert not os.path.exists(str(tmp_path / "pkt.tmp"))


def test_write_bytes_atomic_fsyncs_file_and_parent_dir(tmp_path, monkeypatch):
    """Durability contract, not just atomicity: the tmp file must be
    fsync'd BEFORE the rename and the parent directory AFTER it —
    without both, a host crash after os.replace returns can still lose
    the record the caller was told is durable."""
    import persia_tpu.storage as storage

    synced = []
    real_fsync = os.fsync

    def spy_fsync(fd):
        synced.append(os.path.realpath(f"/proc/self/fd/{fd}")
                      if os.path.exists(f"/proc/self/fd/{fd}") else fd)
        return real_fsync(fd)

    monkeypatch.setattr(storage.os, "fsync", spy_fsync)
    target = tmp_path / "manifest.json"
    PersiaPath(str(target)).write_bytes_atomic(b"payload")
    assert target.read_bytes() == b"payload"
    assert len(synced) == 2
    # first sync is the tmp file (pre-rename), second the parent dir
    assert str(synced[0]).endswith("manifest.json.tmp")
    assert str(synced[1]) == os.path.realpath(str(tmp_path))


def test_write_bytes_atomic_fsync_knob_off(tmp_path, monkeypatch):
    import persia_tpu.storage as storage

    calls = []
    monkeypatch.setattr(storage.os, "fsync", lambda fd: calls.append(fd))
    monkeypatch.setenv("PERSIA_FSYNC", "0")
    p = PersiaPath(str(tmp_path / "pkt"))
    p.write_bytes_atomic(b"x")
    assert p.read_bytes() == b"x"
    assert calls == []  # knob off: atomic rename only, no fsync


# --- SpillStore ----------------------------------------------------------


def test_spill_round_trip_parity(tmp_path):
    s = SpillStore(str(tmp_path), packet_bytes=256)
    rows = {i: np.arange(16, dtype=np.float32) + i for i in range(40)}
    for sign, vec in rows.items():
        s.put(sign, DIM, vec)
    s.flush()
    assert s.stats()["spill_packets"] > 1  # multiple packets exercised
    for sign, vec in rows.items():
        dim, raw = s.take(sign)
        assert dim == DIM
        # bit-identical round trip: the store keeps stored bytes
        np.testing.assert_array_equal(raw.view(np.float32), vec)
    assert len(s) == 0
    assert s.stats()["spill_disk_bytes"] == 0  # drained packets reclaimed


def test_spill_staged_rows_are_readable_before_flush(tmp_path):
    s = SpillStore(str(tmp_path))
    s.put(7, DIM, np.full(16, 3.5, np.float32))
    dim, raw = s.take(7)  # never flushed to disk
    assert dim == DIM
    np.testing.assert_array_equal(raw.view(np.float32),
                                  np.full(16, 3.5, np.float32))


def test_spill_partial_write_cleanup(tmp_path):
    # a torn packet from a crashed writer must be swept at boot, and a
    # fresh store must not index anything from it
    (tmp_path / "spill_00000001.pkt.tmp").write_bytes(b"torn")
    s = SpillStore(str(tmp_path))
    assert not (tmp_path / "spill_00000001.pkt.tmp").exists()
    assert len(s) == 0


def test_spill_missing_file_raises_typed_error(tmp_path):
    s = SpillStore(str(tmp_path), packet_bytes=1)  # flush per put
    s.put(5, DIM, np.arange(16, dtype=np.float32))
    s.flush()
    pkt = [p for p in os.listdir(tmp_path) if p.endswith(".pkt")]
    assert pkt
    os.remove(tmp_path / pkt[0])
    with pytest.raises(SpillReadError):
        s.take(5)
    # the error left the index intact (no silent drop, no corruption)
    assert 5 in s


def test_spill_restart_sweeps_stale_packets(tmp_path):
    # a previous run's packets are unindexable (the index is in-memory
    # only) — a fresh store must sweep them so disk accounting starts
    # from zero and new packet names cannot collide with leftovers
    s = SpillStore(str(tmp_path), packet_bytes=1)
    s.put(5, DIM, np.arange(16, dtype=np.float32))
    s.flush()
    assert [p for p in os.listdir(tmp_path) if p.endswith(".pkt")]
    s2 = SpillStore(str(tmp_path))  # "restart"
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".pkt")]
    assert len(s2) == 0 and s2.stats()["spill_disk_bytes"] == 0


def test_spill_dump_capture_preserves_migrating_rows(tmp_path):
    # a row faulted in (or discarded) between a dump's shard pass and
    # its spill pass must still land in the checkpoint: the capture
    # records it, and its records sort FIRST so newer copies win
    s = SpillStore(str(tmp_path), packet_bytes=1)
    v5 = np.arange(16, dtype=np.float32)
    v6 = np.arange(16, dtype=np.float32) + 100
    s.put(5, DIM, v5)
    s.put(6, DIM, v6)
    s.flush()
    s.start_dump_capture()
    s.take(5)      # fault-in mid-dump
    s.discard(6)   # resident re-insert mid-dump
    cap = s.stop_dump_capture()
    assert set(cap) == {5, 6}
    np.testing.assert_array_equal(cap[5][1].view(np.float32), v5)
    np.testing.assert_array_equal(cap[6][1].view(np.float32), v6)
    # disarmed: later removals are no longer captured
    s.put(7, DIM, v5)
    s.take(7)
    assert s.stop_dump_capture() == {}


def test_holder_dump_keeps_row_faulted_in_mid_dump(tmp_path):
    # the real lost-row race, deterministically: a spilled row is
    # faulted out of the spill index WHILE dump_bytes iterates the
    # spill pass (its shard pass is already over), so without the
    # capture it would appear in neither section of the checkpoint
    h = _armed_holder(capacity=64, spill_dir=str(tmp_path))
    signs = np.arange(1, 301, dtype=np.uint64)
    h.lookup(signs, DIM, training=True)
    h.spill.flush()
    spilled = [s for s in signs.tolist() if s in h.spill]
    assert len(spilled) > 1
    probe = spilled[-1]
    want_dim, want = h.spill.peek(probe)
    orig_items = h.spill.items

    def racing_items():
        gen = orig_items()
        first = next(gen)
        h.spill.take(probe)  # concurrent fault-in mid-spill-pass
        yield first
        yield from gen

    h.spill.items = racing_items
    buf = h.dump_bytes()
    h2 = EmbeddingHolder(capacity=100_000, num_internal_shards=2)
    h2.load_bytes(buf)
    assert len(h2) == len(signs)  # nothing lost
    got = h2.get_entry(probe)
    assert got is not None and got[0] == want_dim
    np.testing.assert_array_equal(got[1], want.view(np.float32))


def test_spill_budget_drops_oldest_packets(tmp_path):
    row = np.arange(64, dtype=np.float32)  # 256 B / row
    s = SpillStore(str(tmp_path), max_bytes=2048, packet_bytes=512)
    for sign in range(40):
        s.put(sign, DIM, row + sign)
    s.flush()
    st = s.stats()
    assert st["spill_disk_bytes"] <= 2048 + 1024  # one packet of slack
    assert st["spill_dropped_rows"] > 0
    # the oldest signs died with their packets; the newest survive
    assert s.take(0) is None
    dim, raw = s.take(39)
    np.testing.assert_array_equal(raw.view(np.float32), row + 39)


# --- holder integration ---------------------------------------------------


def test_holder_spill_fault_in_parity(tmp_path):
    h = _armed_holder(capacity=64, spill_dir=str(tmp_path))
    rng = np.random.default_rng(0)
    signs = rng.choice(10_000, size=1500, replace=False).astype(np.uint64)
    first = h.lookup(signs, DIM, training=True)
    stats = h.spill_stats()
    assert stats["spilled_rows"] > 1000  # capacity 64 forced demotions
    assert len(h) == len(signs)  # one logical table
    # fault-in returns EXACTLY the stored values (training lookups are
    # deterministic per sign, so any loss would show here)
    again = h.lookup(signs, DIM, training=True)
    np.testing.assert_array_equal(first, again)
    assert h.spill_stats()["spill_fault_ins_total"] > 0


def test_holder_gradient_update_faults_spilled_rows_in(tmp_path):
    h = _armed_holder(capacity=32, spill_dir=str(tmp_path))
    signs = np.arange(1, 401, dtype=np.uint64)
    h.lookup(signs, DIM, training=True)
    miss0 = h.gradient_id_miss_count
    h.update_gradients(signs, np.ones((len(signs), DIM), np.float32), DIM)
    # no update fell through the ladder: every sign was found (resident
    # or faulted in), none minted a gradient-id miss
    assert h.gradient_id_miss_count == miss0
    # updates visibly applied on a previously-spilled row
    out = h.lookup(signs[:8], DIM, training=False)
    assert np.isfinite(out).all() and (out != 0).any()


def test_holder_eval_lookup_peeks_without_promotion(tmp_path):
    h = _armed_holder(capacity=32, spill_dir=str(tmp_path))
    signs = np.arange(1, 301, dtype=np.uint64)
    h.lookup(signs, DIM, training=True)
    spilled_before = h.spill_stats()["spilled_rows"]
    assert spilled_before > 0
    # eval reads a spilled row through the ladder...
    out = h.lookup(signs[:50], DIM, training=False)
    assert (np.abs(out).sum(axis=1) > 0).all()  # real values, not zeros
    # ...without mutating tier residency (read-only contract)
    assert h.spill_stats()["spilled_rows"] == spilled_before


def test_holder_half_precision_spill_round_trip(tmp_path):
    h = _armed_holder(capacity=32, spill_dir=str(tmp_path),
                      row_dtype="fp16")
    signs = np.arange(1, 501, dtype=np.uint64)
    first = h.lookup(signs, DIM, training=True)
    again = h.lookup(signs, DIM, training=True)
    # half rows round-trip the spill in their stored byte form:
    # narrow-once semantics survive the demotion bit-exactly
    np.testing.assert_array_equal(first, again)


def test_holder_checkpoint_sees_one_logical_table(tmp_path):
    h = _armed_holder(capacity=48, spill_dir=str(tmp_path / "spill"))
    signs = np.arange(1, 801, dtype=np.uint64)
    h.lookup(signs, DIM, training=True)
    h.update_gradients(signs[:200],
                       np.full((200, DIM), 0.5, np.float32), DIM)
    buf = h.dump_bytes()
    h2 = EmbeddingHolder(capacity=10_000, num_internal_shards=4)
    h2.load_bytes(buf)
    assert len(h2) == len(h) == len(signs)
    for s in (1, 100, 500, 800):
        e1, e2 = h.get_entry(s), h2.get_entry(s)
        assert e1 is not None and e2 is not None
        np.testing.assert_array_equal(e1[1], e2[1])
    # clear drops both rungs
    h.clear()
    assert len(h) == 0 and h.spill_stats()["spilled_rows"] == 0


# --- hotness-admitted device-cache mapper --------------------------------


def test_tiered_mapper_contract_basics():
    m = TieredSignSlotMap(8, window_frac=0.25)
    r = m.assign(np.array([7, 7, 7], np.uint64))
    assert list(r.miss_pos) == [0]
    assert r.slots[0] == r.slots[1] == r.slots[2]
    assert r.n_unique == 1 and list(r.inverse) == [0, 0, 0]
    with pytest.raises(ValueError):
        TieredSignSlotMap(8).assign(
            np.arange(9, dtype=np.uint64))  # distinct > capacity
    # sign 0 eviction is reported via the mask, like the LRU mapper
    m2 = TieredSignSlotMap(2, window_frac=0.5)
    m2.assign(np.array([0, 5], np.uint64))
    r2 = m2.assign(np.array([9], np.uint64))
    assert list(r2.evicted_mask) == [True]


def test_tiered_mapper_pins_current_batch():
    m = TieredSignSlotMap(3, window_frac=0.34)
    m.assign(np.array([1, 2, 3], np.uint64))
    r = m.assign(np.array([1, 4], np.uint64))
    assert r.evicted_mask.sum() == 1
    assert int(r.evicted_signs[r.evicted_mask][0]) != 1  # 1 is pinned


def test_tiered_mapper_slot_space_stays_consistent():
    rng = np.random.default_rng(11)
    m = TieredSignSlotMap(64, window_frac=0.25)
    for _ in range(60):
        signs = rng.integers(0, 500, size=40).astype(np.uint64)
        r = m.assign(signs)
        for u in range(r.n_unique):
            sel = np.nonzero(r.inverse == u)[0]
            assert (r.slots[sel] == r.unique_slots[u]).all()
    signs, slots = m.signs_and_slots()
    assert len(signs) <= 64
    assert len(set(slots.tolist())) == len(slots)  # no slot aliasing


def test_tiered_mapper_beats_lru_under_cold_scan():
    """The point of frequency admission: a zipfian hot set polluted by
    one-touch cold traffic must hit MORE often than pure LRU, because
    cold newcomers churn the window instead of evicting hot rows."""
    rng = np.random.default_rng(3)
    cap, vocab = 500, 10_000
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -1.05
    cdf = np.cumsum(p / p.sum())
    lru, tier = SignSlotMap(cap), TieredSignSlotMap(cap)
    for _ in range(150):
        hot = (np.searchsorted(cdf, rng.random(200)) + 1).astype(np.uint64)
        cold = rng.integers(vocab, vocab * 50, size=60).astype(np.uint64)
        signs = np.concatenate([hot, cold])
        rng.shuffle(signs)
        lru.assign(signs)
        tier.assign(signs)
    assert tier.hit_rate > lru.hit_rate
    assert tier.promotions > 0


def test_tiered_mapper_adapts_to_hot_set_shift():
    """Sketch aging: after traffic shifts to a brand-new hot set, the
    newly hot rows must win protected residency in bounded time — the
    old guard's historical counts decay (W-TinyLFU halving) instead of
    blocking admission forever."""
    rng = np.random.default_rng(9)
    cap = 260
    m = TieredSignSlotMap(cap, window_frac=0.125)
    old_hot = np.arange(1, 150, dtype=np.uint64)
    new_hot = np.arange(10_001, 10_150, dtype=np.uint64)

    def run(hot, batches):
        hits = probes = 0
        for _ in range(batches):
            signs = np.concatenate([
                rng.choice(hot, size=300),
                rng.integers(1 << 20, 1 << 21, size=60)  # cold noise
            ]).astype(np.uint64)
            rng.shuffle(signs)
            h0, p0 = m.hits, m.hits + m.misses
            m.assign(signs)
            hits += m.hits - h0
            probes += (m.hits + m.misses) - p0
        return hits / probes

    run(old_hot, 200)  # old regime: counts pile up for a long time
    late = 0.0
    for chunk in range(6):  # 6 x 25 batches of the new regime
        late = run(new_hot, 25)
    # by the last chunk the new hot set must be serving from the cache
    assert late > 0.6, f"post-shift hit rate stuck at {late:.3f}"


def test_tiered_mapper_promotion_keeps_slot():
    m = TieredSignSlotMap(4, window_frac=0.5)  # hot_cap 2, window 2
    m.assign(np.array([1, 2], np.uint64))      # warm-up -> protected
    r3 = m.assign(np.array([3], np.uint64))    # window
    slot3 = int(r3.slots[0])
    for _ in range(5):  # 3 becomes clearly hotter than protected LRU 1
        m.assign(np.array([3, 2], np.uint64))
    m.assign(np.array([4], np.uint64))         # window fills
    before = m.promotions
    m.assign(np.array([5], np.uint64))         # competition at capacity
    r = m.assign(np.array([3], np.uint64))
    assert int(r.slots[0]) == slot3  # promotion never moved the row
    assert m.promotions >= before


# --- end-to-end: cached training with hotness admission -------------------


def test_cached_hotness_admission_matches_uncached():
    """The ladder's correctness gate: tiny hotness-admitted device cache
    (constant eviction + write-back churn) produces the same losses and
    post-flush PS contents as the flat-PS run."""
    from tests.test_device_cache import _iter_entries, _run

    losses_ref, tables_ref = _run(0, n_batches=8, bs=64)
    import persia_tpu.worker.device_cache as dc

    losses_t, tables_t = None, None
    import os as _os

    _os.environ["PERSIA_TIER_ADMIT"] = "hotness"
    try:
        losses_t, tables_t = _run(280, n_batches=8, bs=64)
    finally:
        _os.environ.pop("PERSIA_TIER_ADMIT", None)
    np.testing.assert_allclose(losses_t, losses_ref, rtol=1e-3, atol=1e-3)
    for tr, tc in zip(tables_ref, tables_t):
        assert set(tr) == set(tc)
        for sign in tr:
            np.testing.assert_allclose(tc[sign], tr[sign], rtol=1e-3,
                                       atol=1e-3, err_msg=f"sign {sign}")


def test_full_ladder_matches_flat_ps_and_flushes_bit_exact(tmp_path):
    """Every rung at once: a hotness-admitted device cache over a PS
    whose RAM rung is squeezed to ~128 rows (the rest lives on disk)
    trains the same stream as flat-PS training to the same losses and
    the same LOGICAL table, and ``flush_device_cache`` lands every
    cached row on the PS bit-identical to the device copy (values AND
    optimizer state), read back through the ladder."""
    from persia_tpu.worker.worker import EmbeddingWorker
    from tests.test_device_cache import _make_ctx, _schema, _zipf_batches

    def run(cache_cap, ladder):
        holders = [_armed_holder(
            capacity=100_000, shards=2,
            capacity_bytes=(1 << 13) if ladder else None,
            spill_dir=str(tmp_path / f"r{i}") if ladder else None)
            for i in range(2)]
        ctx = _make_ctx(EmbeddingWorker(_schema(), holders), cache_cap)
        ctx.device_cache_admission = "hotness" if ladder else None
        losses, flushed = [], 0
        with ctx:
            for b in _zipf_batches(10, 64, vocab=2000):
                loss, _ = ctx.train_step(b)
                losses.append(float(loss))
            if cache_cap:
                eng = ctx._cache_engine
                csigns, cslots = eng.mapper.signs_and_slots()
                ctx.flush_device_cache()
                vals = np.asarray(eng.cache_vals)
                accs = np.asarray(eng.cache_acc)
                for sign, slot in zip(csigns.tolist(), cslots.tolist()):
                    got = next((e for e in (h.get_entry(int(sign))
                                            for h in holders)
                                if e is not None), None)
                    assert got is not None, \
                        f"flushed sign {sign} fell out of the table"
                    d, vec = got
                    np.testing.assert_array_equal(vec[:d], vals[slot][:d])
                    np.testing.assert_array_equal(vec[d:2 * d],
                                                  accs[slot][:d])
                    flushed += 1
        return losses, holders, flushed

    flat_losses, flat_holders, _ = run(0, ladder=False)
    lad_losses, lad_holders, flushed = run(280, ladder=True)
    assert flushed > 0
    np.testing.assert_allclose(lad_losses, flat_losses, rtol=1e-3,
                               atol=1e-3)
    assert sum(h.spill_stats()["spilled_rows_total"]
               for h in lad_holders) > 0, \
        "the squeeze never demoted a row to disk"
    n_rows = 0
    for fh, lh in zip(flat_holders, lad_holders):
        assert len(lh) == len(fh)
        for shard in fh._shards:
            for sign, (d, vec) in shard._map.items():
                got = lh.get_entry(int(sign))
                assert got is not None, f"sign {sign} lost by the ladder"
                np.testing.assert_allclose(got[1][:d], vec[:d], rtol=1e-3,
                                           atol=1e-3)
                n_rows += 1
    assert n_rows > 100


# --- coherence protocol: set_entries version + inc-update + wv rider ------


def test_set_entries_coherence(tmp_path):
    from persia_tpu.inc_update import IncrementalUpdateDumper
    from persia_tpu.service.ps_service import PsClient, PsService

    holder = _armed_holder(capacity=10_000)
    dumper = IncrementalUpdateDumper(holder, str(tmp_path / "inc"),
                                     buffer_size=10_000)
    svc = PsService(holder, port=0, inc_dumper=dumper)
    svc.server.serve_background()
    try:
        armed = PsClient(svc.addr, hotness=True)
        legacy = PsClient(svc.addr, hotness=False)
        v0 = armed.health()["update_version"]
        signs = np.arange(1, 9, dtype=np.uint64)
        vecs = np.ones((8, 2 * DIM), np.float32)
        armed.set_entries(signs, DIM, vecs)
        # versioned write-back: the rider answered, the version stream
        # advanced, and the write landed in the inc-update buffer
        assert armed.last_writeback_ver == v0 + 1
        assert armed.health()["update_version"] == v0 + 1
        assert len(dumper._buffer) >= len(signs)
        # legacy client: same RPC, empty reply, version still advances
        legacy.set_entries(signs, DIM, vecs)
        assert legacy.last_writeback_ver is None
        assert legacy.health()["update_version"] == v0 + 2
        armed.client.close()
        legacy.client.close()
    finally:
        svc.stop()


def test_set_entries_wire_byte_identical_when_off():
    """Ladder off (telemetry unarmed): the set_entries request framing
    must be byte-identical to the legacy wire."""
    from persia_tpu.rpc import pack_arrays_sg
    from persia_tpu.service.ps_service import PsClient

    cli = PsClient.__new__(PsClient)  # framing only; no socket
    cli.telemetry = False
    cli._pack = pack_arrays_sg

    def join(b):
        return b if isinstance(b, (bytes, bytearray)) else b"".join(
            bytes(x) for x in b)

    signs = np.arange(4, dtype=np.uint64)
    vecs = np.ones((4, 2 * DIM), np.float32)
    meta = {"dim": DIM}
    got = pack_arrays_sg(meta, [signs, vecs])
    # replicate set_entries' payload construction with telemetry off
    if cli.telemetry:
        meta["wv"] = 1
    ours = cli._pack(meta, [np.ascontiguousarray(signs, np.uint64),
                            np.ascontiguousarray(vecs, np.float32)])
    assert join(ours) == join(got)


# --- planner byte math follows the live row dtype -------------------------


def test_planner_row_bytes_from_live_holder():
    from persia_tpu import hotness as hot

    snaps = []
    for dtype, itemsize in (("fp32", 4), ("fp16", 2)):
        h = _armed_holder(capacity=100_000, hotness=True, row_dtype=dtype)
        h.lookup(np.arange(1, 2001, dtype=np.uint64), DIM, training=True)
        snap = h.hotness_snapshot()
        # the snapshot stamps the holder's true storage width...
        assert snap["tables"][str(DIM)]["row_bytes"] == DIM * itemsize
        snaps.append(snap)
        plan = hot.planner_report(snap, hbm_bytes=1 << 20)
        # ...but the HBM plan floors it at the fp32 import width: the
        # device cache holds f32 values whatever the PS tier stores,
        # so an fp16 PS must NOT double the planned hot rows
        assert plan["tables"][0]["row_bytes"] == DIM * 4
    p32 = hot.planner_report(snaps[0], hbm_bytes=4096)["tables"][0]
    p16 = hot.planner_report(snaps[1], hbm_bytes=4096)["tables"][0]
    assert p16["hot_rows"] == p32["hot_rows"]
    # a caller override (e.g. a narrow-storage device cache of the
    # future) wins outright over the floor
    pov = hot.planner_report(
        snaps[1], hbm_bytes=4096,
        row_bytes={str(DIM): DIM * 2})["tables"][0]
    assert pov["row_bytes"] == DIM * 2
    assert pov["hot_rows"] == 2 * p32["hot_rows"]
    # the merge carries row_bytes (conservative max on a mixed fleet)
    merged = hot.merge_snapshots(snaps)
    assert merged["tables"][str(DIM)]["row_bytes"] == DIM * 4


def test_device_cache_hit_collapse_rule_registered():
    from persia_tpu.slos import SloEngine, default_rules

    names = {r.name for r in default_rules()}
    assert "device_cache_hit_collapse" in names
    eng = SloEngine(default_rules())
    eng.ingest("trainer", [("some_other_metric", {}, 1.0)])
    alerts = {a["rule"]: a for a in eng.evaluate()}
    assert not alerts["device_cache_hit_collapse"]["firing"]
