"""Device-resident embedding cache: mapper semantics, parity with the
uncached PS path, eviction write-back, and the flush-for-eval contract."""

import numpy as np
import optax
import pytest

from persia_tpu.config import EmbeddingSchema, uniform_slots
from persia_tpu.ctx import TrainCtx, eval_ctx
from persia_tpu.data.batch import (
    IDTypeFeatureWithSingleID,
    Label,
    NonIDTypeFeature,
    PersiaBatch,
)
from persia_tpu.embedding import EmbeddingConfig
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.models import DLRM
from persia_tpu.worker.device_cache import SignSlotMap, VictimBuffer
from persia_tpu.worker.worker import EmbeddingWorker

DIM = 8
NUM_SLOTS = 4
SLOTS = [f"s{i}" for i in range(NUM_SLOTS)]


# --- SignSlotMap ---------------------------------------------------------


def test_mapper_hit_miss_evict_order():
    m = SignSlotMap(3)
    r = m.assign(np.array([10, 11, 12], np.uint64))
    assert len(set(r.slots)) == 3 and list(r.miss_pos) == [0, 1, 2]
    assert not r.evicted_mask.any()  # free slots, nothing evicted
    # touch 10 (refresh), then force one eviction: LRU is now 11
    m.assign(np.array([10], np.uint64))
    r2 = m.assign(np.array([13], np.uint64))
    assert list(r2.evicted_signs) == [11] and list(r2.evicted_mask) == [True]
    # 11 is gone, 13 present
    r3 = m.assign(np.array([13, 11], np.uint64))
    assert list(r3.miss_pos) == [1]
    assert r3.slots[0] == r2.slots[0]


def test_mapper_pins_current_batch_signs():
    m = SignSlotMap(3)
    m.assign(np.array([1, 2, 3], np.uint64))
    # batch contains 1 (LRU) AND a miss; the victim must not be 1 even
    # though it is least-recently-used BEFORE this batch touches it
    r = m.assign(np.array([1, 4], np.uint64))
    assert list(r.evicted_signs) == [2] and list(r.evicted_mask) == [True]


def test_mapper_duplicate_miss_in_batch():
    m = SignSlotMap(4)
    r = m.assign(np.array([7, 7, 7], np.uint64))
    assert list(r.miss_pos) == [0]  # one allocation
    assert r.slots[0] == r.slots[1] == r.slots[2]
    # dedup map: all three positions share one distinct index
    assert r.n_unique == 1 and list(r.inverse) == [0, 0, 0]
    assert r.unique_slots[0] == r.slots[0]


def test_mapper_evicted_sign_zero_is_masked():
    """Sign 0 is legal; its eviction must be reported via the mask."""
    m = SignSlotMap(2)
    m.assign(np.array([0, 5], np.uint64))
    m.assign(np.array([5], np.uint64))     # sign 0 becomes LRU
    r = m.assign(np.array([9], np.uint64))
    assert list(r.evicted_signs) == [0] and list(r.evicted_mask) == [True]


def test_mapper_rejects_oversized_batch():
    m = SignSlotMap(2)
    with pytest.raises(ValueError):
        m.assign(np.array([1, 2, 3], np.uint64))


def test_native_mapper_matches_python(native_lib_path):
    """Randomized trace: the C++ mapper must produce identical slots,
    miss positions, and eviction choices to the python reference."""
    from persia_tpu.worker.device_cache import NativeSignSlotMap

    rng = np.random.default_rng(7)
    py = SignSlotMap(50)
    nat = NativeSignSlotMap(50)
    for _ in range(60):
        # skewed draws incl. duplicates; distinct-per-batch < capacity
        signs = (rng.zipf(1.3, size=30) % 120).astype(np.uint64)
        pr = py.assign(signs)
        nr = nat.assign(signs)
        # slot NUMBERS may differ (allocation order); the MAPPING must
        # agree: same sign -> same slot within a batch, same miss set,
        # same eviction victims, same dedup structure
        np.testing.assert_array_equal(pr.miss_pos, nr.miss_pos)
        np.testing.assert_array_equal(pr.evicted_signs, nr.evicted_signs)
        np.testing.assert_array_equal(pr.evicted_mask, nr.evicted_mask)
        np.testing.assert_array_equal(pr.inverse, nr.inverse)
        assert pr.n_unique == nr.n_unique
        for u in range(pr.n_unique):
            # distinct index u maps to the slot its positions use
            sel = np.nonzero(pr.inverse == u)[0]
            assert (pr.slots[sel] == pr.unique_slots[u]).all()
            assert (nr.slots[sel] == nr.unique_slots[u]).all()
        for s in np.unique(signs):
            sel = np.nonzero(signs == s)[0]
            assert len(set(pr.slots[sel])) == 1
            assert len(set(nr.slots[sel])) == 1
        assert len(py) == len(nat)
    assert py.hits == nat.hits and py.misses == nat.misses
    assert py.evictions == nat.evictions
    # full working set agrees
    psigns, _ = py.signs_and_slots()
    nsigns, _ = nat.signs_and_slots()
    assert set(psigns.tolist()) == set(nsigns.tolist())


def test_native_mapper_rejects_oversized_batch(native_lib_path):
    from persia_tpu.worker.device_cache import NativeSignSlotMap

    m = NativeSignSlotMap(2)
    with pytest.raises(ValueError):
        m.assign(np.array([1, 2, 3], np.uint64))


@pytest.mark.parametrize("make", [SignSlotMap, "native"])
def test_mapper_oversized_batch_leaves_state_intact(make, request):
    """A rejected batch must not mutate the map (both backends): a
    half-applied assign would leave signs mapped to slots whose rows
    were never imported — later hits on them would read garbage."""
    if make == "native":
        # only the native param needs the built lib; the pure-python
        # invariant must stay covered on toolchain-less machines (the
        # exact machines that fall back to SignSlotMap in production)
        request.getfixturevalue("native_lib_path")
        from persia_tpu.worker.device_cache import NativeSignSlotMap as make

    m = make(4)
    first = m.assign(np.array([10, 11], np.uint64))
    with pytest.raises(ValueError):
        m.assign(np.array([1, 2, 3, 4, 5], np.uint64))
    assert len(m) == 2
    signs, slots = m.signs_and_slots()
    by_sign = dict(zip(signs.tolist(), slots.tolist()))
    assert set(by_sign) == {10, 11}
    assert by_sign[10] == first.slots[0] and by_sign[11] == first.slots[1]
    # a batch with many DUPLICATES but few distinct signs must still fit
    # (n > capacity, distinct <= capacity)
    dup = np.array([7, 7, 7, 7, 8, 8], np.uint64)
    r = m.assign(dup)
    assert r.n_unique == 2
    # and the map still serves correct hits afterwards (capacity 4 holds
    # all four signs — nothing was evicted along the way)
    again = m.assign(np.array([10, 7], np.uint64))
    assert again.slots[0] == by_sign[10]
    assert m.misses == 4 and m.evictions == 0


def test_victim_buffer_token_matching():
    v = VictimBuffer()
    v.put(5, "old", token=1)
    v.put(5, "new", token=2)  # newer eviction overwrites
    assert v.take_if(5, 1) is None  # stale job cannot steal
    assert v.take_if(5, 2) == "new"
    assert len(v) == 0


# --- end-to-end parity ---------------------------------------------------


def _schema():
    return EmbeddingSchema(slots_config=uniform_slots(SLOTS, dim=DIM))


def _make_ctx(worker, cache_capacity=0, seed=3, mesh=None, schema=None):
    from persia_tpu.config import CommonConfig, GlobalConfig

    return TrainCtx(
        model=DLRM(embedding_dim=DIM),
        dense_optimizer=optax.adagrad(0.05),
        embedding_optimizer=Adagrad(lr=0.05),
        schema=schema or _schema(),
        worker=worker,
        embedding_config=EmbeddingConfig(emb_initialization=(-0.05, 0.05)),
        # f32 wire so the uncached run is comparable at float tolerance
        # (the cached path is f32 end-to-end — no wire)
        global_config=GlobalConfig(
            common=CommonConfig(embedding_wire_dtype="f32")),
        seed=seed,
        device_cache_capacity=cache_capacity,
        mesh=mesh,
    )


def _zipf_batches(n_batches, bs, vocab=400, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n_batches):
        # skewed ids (the cache's target distribution), distinct range per
        # slot, +1 keeps sign 0 out
        ids = rng.zipf(1.5, size=(bs, NUM_SLOTS)) % vocab
        signs = (ids + np.arange(NUM_SLOTS) * vocab + 1).astype(np.uint64)
        dense = rng.normal(size=(bs, 13)).astype(np.float32)
        label = (rng.random((bs, 1)) < 0.3).astype(np.float32)
        yield PersiaBatch(
            [IDTypeFeatureWithSingleID(SLOTS[s],
                                       np.ascontiguousarray(signs[:, s]))
             for s in range(NUM_SLOTS)],
            non_id_type_features=[NonIDTypeFeature(dense)],
            labels=[Label(label)],
            requires_grad=True,
            batch_id=i,
        )


def _run(cache_capacity, n_batches=12, bs=64, holder_factory=None,
         mesh=None):
    from persia_tpu.ps.store import EmbeddingHolder

    factory = holder_factory or (lambda: EmbeddingHolder(100_000, 2))
    worker = EmbeddingWorker(_schema(), [factory(), factory()])
    ctx = _make_ctx(worker, cache_capacity, mesh=mesh)
    losses = []
    with ctx:
        for b in _zipf_batches(n_batches, bs):
            loss, _ = ctx.train_step(b)
            losses.append(float(loss))
        if cache_capacity:
            assert ctx._cache_engine.hit_rate > 0.5  # zipf => mostly hits
            ctx.flush_device_cache()
        # PS contents after flush are the comparable artifact (python
        # holder only; the native store is compared via losses)
        tables = []
        for c in worker.ps_clients:
            if not hasattr(c, "_shards"):
                tables.append({})
                continue
            entries = {}
            for sign, (d, vec) in _iter_entries(c):
                entries[sign] = vec[:d].copy()
            tables.append(entries)
    return losses, tables


def _iter_entries(holder):
    # EmbeddingHolder python backend: walk shards
    for shard in holder._shards:
        for sign, (dim, vec) in list(shard._map.items()):
            yield sign, (dim, vec)


def test_cached_matches_uncached_exactly():
    """Same stream, wire f32 vs on-device f32: the cached path must
    produce the same PS contents and losses as the uncached path to
    float tolerance (same Adagrad math, same dedup-sum semantics)."""
    import persia_tpu.ctx as ctx_mod

    losses_ref, tables_ref = _run(0)
    losses_cached, tables_cached = _run(4096)
    np.testing.assert_allclose(losses_cached, losses_ref, rtol=1e-3,
                               atol=1e-3)
    total = 0
    for tr, tc in zip(tables_ref, tables_cached):
        assert set(tr) == set(tc)
        for sign in tr:
            np.testing.assert_allclose(
                tc[sign], tr[sign], rtol=1e-3, atol=1e-3,
                err_msg=f"sign {sign}")
            total += 1
    assert total > 100


def test_eviction_writeback_preserves_rows():
    """A tiny cache (constant eviction + write-back + re-admission with
    state import) must STILL produce exactly the uncached run's PS
    contents — eviction churn is not allowed to lose or corrupt
    updates."""
    losses_ref, tables_ref = _run(0, n_batches=10, bs=64)
    losses_tiny, tables_tiny = _run(280, n_batches=10, bs=64)
    np.testing.assert_allclose(losses_tiny, losses_ref, rtol=1e-3,
                               atol=1e-3)
    for tr, tc in zip(tables_ref, tables_tiny):
        assert set(tr) == set(tc)
        for sign in tr:
            np.testing.assert_allclose(tc[sign], tr[sign], rtol=1e-3,
                                       atol=1e-3, err_msg=f"sign {sign}")


def test_eval_ctx_flushes_cache():
    from persia_tpu.ps.store import EmbeddingHolder

    worker = EmbeddingWorker(_schema(), [EmbeddingHolder(100_000, 2)])
    ctx = _make_ctx(worker, cache_capacity=4096)
    batches = list(_zipf_batches(6, 64))
    with ctx:
        for b in batches:
            ctx.train_step(b)
        with eval_ctx(ctx) as ectx:
            for b in batches[:2]:
                b.requires_grad = False
                pred, labels = ectx.forward(b)
                assert np.isfinite(np.asarray(pred)).all()
        # flush happened: for every cached sign the PS copy equals the
        # device row exactly
        eng = ctx._cache_engine
        signs, slots = eng.mapper.signs_and_slots()
        assert len(signs) > 50
        cache_np = np.asarray(eng.cache_vals)
        checked = 0
        for sign, slot in zip(signs[:200], slots[:200]):
            ent = worker.ps_clients[0].get_entry(int(sign))
            if ent is None:
                continue  # routed to another replica in multi-PS setups
            d, vec = ent
            np.testing.assert_allclose(vec[:d], cache_np[slot], rtol=1e-6,
                                       atol=1e-6)
            checked += 1
        assert checked > 20


def test_cached_parity_native_holder(native_lib_path):
    """Same parity through the C++ store (ctypes get_entry/set_entry)."""
    from persia_tpu.ps.native import NativeEmbeddingHolder

    def factory():
        return NativeEmbeddingHolder(100_000, 2)

    losses_ref, _ = _run(0, n_batches=6, bs=64, holder_factory=factory)
    losses_cached, _ = _run(512, n_batches=6, bs=64,
                            holder_factory=factory)
    np.testing.assert_allclose(losses_cached, losses_ref, rtol=1e-3,
                               atol=1e-3)


def test_cached_training_over_native_ps_service(native_lib_path):
    """Device cache against the C++ persia-embedding-ps binary over RPC:
    miss import (lookup + batched get_entries) and eviction write-back
    (batched set_entries) cross the real wire. Tiny cache forces churn."""
    from persia_tpu.service.helper import ServiceCtx
    from persia_tpu.service.ps_service import PsClient

    with ServiceCtx(_schema(), n_workers=1, n_ps=2, native_ps=True,
                    ps_capacity=100_000, ps_num_shards=4) as svc:
        worker = EmbeddingWorker(_schema(),
                                 [PsClient(a) for a in svc.ps_addrs])
        ctx = _make_ctx(worker, cache_capacity=300)
        with ctx:
            losses = []
            for b in _zipf_batches(8, 64, seed=11):
                loss, _ = ctx.train_step(b)
                losses.append(float(loss))
            assert np.isfinite(losses).all()
            written = ctx.flush_device_cache()
            assert written > 0
        total = sum(len(PsClient(a)) for a in svc.ps_addrs)
        assert total > 50  # rows landed across both replicas


def test_load_checkpoint_invalidates_cache(tmp_path):
    """Restore must not serve (or later flush) pre-load cached rows."""
    from persia_tpu.ps.store import EmbeddingHolder

    worker = EmbeddingWorker(_schema(), [EmbeddingHolder(100_000, 2)])
    ctx = _make_ctx(worker, cache_capacity=4096)
    batches = list(_zipf_batches(4, 64))
    with ctx:
        for b in batches:
            ctx.train_step(b)
        ctx.dump_checkpoint(str(tmp_path), with_dense=False)
        for b in batches:  # diverge past the checkpoint
            ctx.train_step(b)
        eng = ctx._cache_engine
        assert len(eng.mapper) > 0
        ctx.load_checkpoint(str(tmp_path), with_dense=False)
        # cache dropped: nothing to serve stale hits or flush stale rows
        assert len(eng.mapper) == 0 and len(eng.victims) == 0
        # training resumes from restored values (all misses re-import)
        loss, _ = ctx.train_step(batches[0])
        assert np.isfinite(float(loss))


def test_cache_rejects_unsupported_shapes():
    from persia_tpu.ps.store import EmbeddingHolder

    worker = EmbeddingWorker(_schema(), [EmbeddingHolder(1000, 2)])
    from persia_tpu.embedding.optim import SGD

    ctx = TrainCtx(
        model=DLRM(embedding_dim=DIM),
        dense_optimizer=optax.adagrad(0.05),
        embedding_optimizer=SGD(lr=0.05),
        schema=_schema(),
        worker=worker,
        device_cache_capacity=64,
    )
    with ctx:
        b = next(_zipf_batches(1, 8))
        with pytest.raises(NotImplementedError):
            ctx.train_step(b)


def test_cached_matches_uncached_on_mesh():
    """The v2 envelope's mesh support: under the 8-device CPU mesh the
    cache is ONE GSPMD row-sharded array — same program, partitioned —
    so losses AND post-flush PS contents must match the unmeshed
    uncached run to float tolerance (the same gate that certifies v1)."""
    import jax

    from persia_tpu.parallel.mesh import make_mesh

    losses_ref, tables_ref = _run(0, n_batches=8, bs=64)
    mesh = make_mesh((8, 1))
    losses_mesh, tables_mesh = _run(2048, n_batches=8, bs=64, mesh=mesh)
    np.testing.assert_allclose(losses_mesh, losses_ref, rtol=1e-3,
                               atol=1e-3)
    total = 0
    for tr, tc in zip(tables_ref, tables_mesh):
        assert set(tr) == set(tc)
        for sign in tr:
            np.testing.assert_allclose(tc[sign], tr[sign], rtol=1e-3,
                                       atol=1e-3, err_msg=f"sign {sign}")
            total += 1
    assert total > 100


def test_cached_mesh_arrays_actually_sharded():
    """The cache arrays must really be laid out across the mesh (not
    silently replicated — the HBM-scaling claim depends on it)."""
    from persia_tpu.parallel.mesh import make_mesh
    from persia_tpu.ps.store import EmbeddingHolder

    mesh = make_mesh((4, 2))
    worker = EmbeddingWorker(_schema(), [EmbeddingHolder(100_000, 2)])
    ctx = _make_ctx(worker, cache_capacity=1024, mesh=mesh)
    with ctx:
        for b in _zipf_batches(2, 32):
            ctx.train_step(b)
        eng = ctx._cache_engine
        shardings = {tuple(s.index) for s in
                     eng.cache_vals.addressable_shards}
        assert len(shardings) == 8  # 8 distinct row ranges, one per device
        # rows axis padded to a device-count multiple, dummy row intact
        assert eng.cache_vals.shape[0] % 8 == 0
        assert eng.cache_vals.shape[0] >= 1024 + 1


def _bag_schema():
    from persia_tpu.config import SlotConfig

    # two plain summed bags + one sqrt-scaled bag (middleware parity)
    return EmbeddingSchema(slots_config={
        "b0": SlotConfig(name="b0", dim=DIM),
        "b1": SlotConfig(name="b1", dim=DIM),
        "b2": SlotConfig(name="b2", dim=DIM, sqrt_scaling=True),
    })


def _bag_batches(n_batches, bs, vocab=300, seed=0):
    from persia_tpu.data.batch import IDTypeFeature

    rng = np.random.default_rng(seed)
    for i in range(n_batches):
        feats = []
        for s, name in enumerate(["b0", "b1", "b2"]):
            # variable bag sizes incl. empty bags; duplicate ids within
            # a bag are legal and must count twice
            rows = [
                ((rng.zipf(1.5, size=rng.integers(0, 4)) % vocab)
                 + s * vocab + 1).astype(np.uint64)
                for _ in range(bs)
            ]
            feats.append(IDTypeFeature(name, rows))
        dense = rng.normal(size=(bs, 13)).astype(np.float32)
        label = (rng.random((bs, 1)) < 0.3).astype(np.float32)
        yield PersiaBatch(
            feats,
            non_id_type_features=[NonIDTypeFeature(dense)],
            labels=[Label(label)],
            requires_grad=True,
            batch_id=i,
        )


def _run_bags(cache_capacity, n_batches=8, bs=64, mesh=None):
    from persia_tpu.ps.store import EmbeddingHolder

    worker = EmbeddingWorker(_bag_schema(),
                             [EmbeddingHolder(100_000, 2),
                              EmbeddingHolder(100_000, 2)])
    ctx = _make_ctx(worker, cache_capacity, mesh=mesh,
                    schema=_bag_schema())
    losses = []
    with ctx:
        for b in _bag_batches(n_batches, bs):
            loss, _ = ctx.train_step(b)
            losses.append(float(loss))
        if cache_capacity:
            ctx.flush_device_cache()
        tables = []
        for c in worker.ps_clients:
            entries = {}
            for sign, (d, vec) in _iter_entries(c):
                entries[sign] = vec[:d].copy()
            tables.append(entries)
    return losses, tables


def test_cached_multi_id_bags_match_uncached():
    """Multi-id summed bags (variable length, empty bags, duplicate ids,
    one sqrt-scaled slot) through the segment-sum cached step must match
    the uncached middleware path: same losses, same PS contents."""
    losses_ref, tables_ref = _run_bags(0)
    losses_cached, tables_cached = _run_bags(2048)
    np.testing.assert_allclose(losses_cached, losses_ref, rtol=1e-3,
                               atol=1e-3)
    total = 0
    for tr, tc in zip(tables_ref, tables_cached):
        assert set(tr) == set(tc)
        for sign in tr:
            np.testing.assert_allclose(tc[sign], tr[sign], rtol=1e-3,
                                       atol=1e-3, err_msg=f"sign {sign}")
            total += 1
    assert total > 50


def test_cached_multi_id_bags_on_mesh_with_eviction():
    """Bags + mesh + a tiny cache (eviction churn) together."""
    from persia_tpu.parallel.mesh import make_mesh

    losses_ref, tables_ref = _run_bags(0, n_batches=6)
    losses_c, tables_c = _run_bags(160, n_batches=6,
                                   mesh=make_mesh((8, 1)))
    np.testing.assert_allclose(losses_c, losses_ref, rtol=1e-3, atol=1e-3)
    for tr, tc in zip(tables_ref, tables_c):
        assert set(tr) == set(tc)
        for sign in tr:
            np.testing.assert_allclose(tc[sign], tr[sign], rtol=1e-3,
                                       atol=1e-3, err_msg=f"sign {sign}")


def test_cache_over_remote_worker_fails_at_construction():
    """RemoteEmbeddingWorker carries no lookup_rows_with_state/set_rows
    RPC, so ServiceCtx -> remote_worker() -> device_cache_capacity= used
    to die with an AttributeError inside the first train_step. It must
    be refused where it is configured, and say what works instead."""
    from persia_tpu.service.worker_service import RemoteEmbeddingWorker

    remote = RemoteEmbeddingWorker(["127.0.0.1:1"])  # connects lazily
    with pytest.raises(TypeError, match="lookup_rows_with_state"):
        _make_ctx(remote, cache_capacity=64)
    _make_ctx(remote, cache_capacity=0)  # uncached over RPC stays fine
