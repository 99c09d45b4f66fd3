"""Mixed-precision embedding tier: storage policy, wire codec, budgets.

Covers the PR-5 tentpole end to end:

- ``RowPrecision`` widen/narrow round trips (exactness for representable
  values, bounded relative error otherwise, optimizer state bit-exact)
- update-math fp32-parity of half-precision holders against a pure-fp32
  holder, per optimizer, with a documented rel-err budget
- ``__codec__`` negotiation old<->new in BOTH directions, with the
  byte-identical-legacy-wire property pinned via served-request counts
  (the same discipline as test_dataplane/test_faults)
- PSD v2 checkpoint round trips + forward/back compat with v1, incl.
  the streaming reader and fp16 incremental-update packets
- int8-gradient error-feedback convergence smoke through the REAL
  worker/PS path
- byte-accounted eviction (fp16 admits ~2x the rows), resident-bytes
  observability, and the native-backend config lint
"""

import struct

import numpy as np
import pytest

from persia_tpu.ps.optim import RowPrecision
from persia_tpu.ps.store import DUMP_MAGIC, EmbeddingHolder, EvictionMap
from persia_tpu.service.ps_service import PsClient, PsService
from persia_tpu.worker.middleware import GradErrorFeedback

DIM = 8

ADAGRAD = {"type": "adagrad", "lr": 0.05, "initialization": 0.1,
           "g_square_momentum": 1.0, "vectorwise_shared": False}
SGD = {"type": "sgd", "lr": 0.05}
ADAM = {"type": "adam", "lr": 0.01}

# documented per-write narrowing bounds (docs/ARCHITECTURE.md
# "Precision & memory budget"): fp16 rounds to 11 significand bits,
# bf16 to 8
NARROW_REL = {"fp16": 2.0 ** -11, "bf16": 2.0 ** -8}


def _mk_holder(row_dtype="fp32", optimizer=ADAGRAD, capacity=100_000,
               shards=4, capacity_bytes=None):
    h = EmbeddingHolder(capacity, shards, row_dtype=row_dtype,
                        capacity_bytes=capacity_bytes)
    h.configure("bounded_uniform", {"lower": -0.01, "upper": 0.01})
    if optimizer is not None:
        h.register_optimizer(dict(optimizer))
    return h


# --------------------------------------------------------------------------
# widen/narrow round trips
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["fp16", "bf16"])
def test_rowprecision_roundtrip_bounds(name):
    rp = RowPrecision(name)
    rng = np.random.default_rng(0)
    full = rng.normal(scale=0.1, size=24).astype(np.float32)
    stored = rp.pack(full, DIM)
    back = rp.unpack(stored, DIM)
    # embedding slice: one narrowing, bounded relative error
    emb, emb_back = full[:DIM], back[:DIM]
    rel = np.abs(emb - emb_back) / np.maximum(np.abs(emb), 1e-12)
    assert rel.max() <= NARROW_REL[name]
    # optimizer state stays fp32 BIT-exact
    np.testing.assert_array_equal(full[DIM:], back[DIM:])
    # narrow-then-widen is idempotent: a second round trip is exact
    stored2 = rp.pack(back, DIM)
    np.testing.assert_array_equal(rp.unpack(stored2, DIM), back)
    # byte math
    assert stored.nbytes == rp.entry_nbytes(DIM, 16)
    assert rp.emb_nbytes(DIM) == DIM * (2 if name in ("fp16", "bf16") else 4)


def test_rowprecision_fp32_is_legacy_layout():
    rp = RowPrecision("fp32")
    full = np.arange(12, dtype=np.float32)
    stored = rp.pack(full, DIM)
    assert stored.dtype == np.float32 and stored is full  # no copy, no wrap
    assert rp.stored_len(DIM, 4) == 12


def test_rowprecision_rejects_unknown():
    with pytest.raises(ValueError, match="row_dtype"):
        RowPrecision("fp8")


# --------------------------------------------------------------------------
# update-math fp32-parity per optimizer
# --------------------------------------------------------------------------


@pytest.mark.parametrize("opt,budget", [
    (SGD, 3e-3), (ADAGRAD, 3e-3), (ADAM, 3e-3)])
@pytest.mark.parametrize("row_dtype", ["fp16", "bf16"])
def test_update_parity_vs_fp32_holder(opt, budget, row_dtype):
    """K update steps on a half holder track a pure-fp32 holder within
    the per-optimizer budget: the update arithmetic itself is fp32 (the
    widen-on-read/narrow-on-write contract), so the only divergence is
    the once-per-write narrowing of the embedding slice."""
    if row_dtype == "bf16":
        budget = 3e-2  # 8 significand bits
    ref = _mk_holder("fp32", opt)
    half = _mk_holder(row_dtype, opt)
    rng = np.random.default_rng(1)
    signs = rng.integers(1, 1 << 40, size=256, dtype=np.uint64)
    for h in (ref, half):
        h.lookup(signs, DIM, True)
    for _ in range(10):
        g = rng.normal(scale=0.05, size=(len(signs), DIM)).astype(np.float32)
        for h in (ref, half):
            h.update_gradients(signs, g, DIM)
    a = ref.lookup(signs, DIM, False)
    b = half.lookup(signs, DIM, False)
    scale = max(np.abs(a).max(), 1e-6)
    assert np.abs(a - b).max() / scale <= budget
    # duplicate signs keep the sequential-apply semantics on both paths
    dup = np.array([signs[0], signs[0], signs[1]], np.uint64)
    gd = np.full((3, DIM), 0.01, np.float32)
    for h in (ref, half):
        h.update_gradients(dup, gd, DIM)
    a = ref.lookup(signs[:2], DIM, False)
    b = half.lookup(signs[:2], DIM, False)
    assert np.abs(a - b).max() / scale <= budget


def test_optimizer_state_stays_fp32_exact():
    """Adagrad accumulators must be BIT-identical between fp32 and fp16
    holders after identical updates — state never narrows."""
    ref = _mk_holder("fp32", ADAGRAD)
    half = _mk_holder("fp16", ADAGRAD)
    signs = np.arange(1, 65, dtype=np.uint64)
    for h in (ref, half):
        h.lookup(signs, DIM, True)
    g = np.full((len(signs), DIM), 0.25, np.float32)
    # the two holders' EMB slices diverge (narrowed), so the grad^2
    # accumulation inputs are identical only on the first step
    for h in (ref, half):
        h.update_gradients(signs, g, DIM)
    for s in signs[:8]:
        np.testing.assert_array_equal(ref.get_entry(int(s))[1][DIM:],
                                      half.get_entry(int(s))[1][DIM:])


# --------------------------------------------------------------------------
# codec negotiation + byte-identical legacy wire
# --------------------------------------------------------------------------


def _svc(holder, **kw):
    svc = PsService(holder, port=0, **kw)
    svc.server.serve_background()
    return svc


def test_codec_off_sends_no_probe_wire_byte_identical():
    """With the codec off (the default), the client never probes
    ``__codec__`` — the served-request counter sees exactly the data
    calls, so the wire is byte-identical to the legacy protocol."""
    svc = _svc(_mk_holder())
    try:
        c = PsClient(svc.addr, wire_codec="off")
        c.lookup(np.arange(1, 9, dtype=np.uint64), DIM, False)
        # lookup only — no __codec__ (and no __trace__/__deadline__)
        assert svc.server.health()["served_rpcs"] == 1
    finally:
        svc.stop()


def test_codec_new_client_legacy_server_negotiates_down():
    """enable_codec=False emulates a legacy server: it answers the
    probe 'no such method' and the connection stays on the fp32 wire —
    lookups and int8-policy updates still work, encoded fp32."""
    h = _mk_holder()
    svc = _svc(h, )
    svc.server._enable_codec = False
    try:
        c = PsClient(svc.addr, wire_codec="fp16+int8")
        signs = np.arange(1, 33, dtype=np.uint64)
        out = c.lookup(signs, DIM, True)
        assert out.dtype == np.float32
        assert c.client.codec_active() is False
        before = h.lookup(signs, DIM, False).copy()
        c.update_gradients(signs, np.ones((32, DIM), np.float32), DIM)
        assert not np.array_equal(before, h.lookup(signs, DIM, False))
    finally:
        svc.stop()


def test_codec_refusing_server_answers_fp32_even_to_fp16_request():
    """The enable_codec=False legacy-emulation lever must revert EVERY
    codec surface: a raw 'resp: fp16' request meta (no negotiation) is
    ignored and the rows come back fp32."""
    from persia_tpu.rpc import RpcClient, pack_arrays, unpack_arrays

    h = _mk_holder()
    svc = _svc(h)
    svc.server._enable_codec = False
    try:
        c = RpcClient(svc.addr)
        signs = np.arange(1, 9, dtype=np.uint64)
        h.lookup(signs, DIM, True)
        resp = c.call("lookup", pack_arrays(
            {"dim": DIM, "training": False, "resp": "fp16"}, [signs]))
        meta, (rows,) = unpack_arrays(resp)
        assert "codec" not in meta and rows.dtype == np.float32
    finally:
        svc.stop()


def test_codec_legacy_client_new_server_stays_fp32():
    h = _mk_holder()
    svc = _svc(h)
    try:
        served0 = svc.server.health()["served_rpcs"]
        c = PsClient(svc.addr, wire_codec="off")
        out = c.lookup(np.arange(1, 9, dtype=np.uint64), DIM, True)
        assert out.dtype == np.float32
        assert svc.server.health()["served_rpcs"] == served0 + 1
    finally:
        svc.stop()


@pytest.mark.parametrize("row_dtype", ["fp32", "fp16"])
def test_codec_fp16_lookup_and_int8_update_roundtrip(row_dtype):
    """New client <-> new server: lookups travel fp16 (and round-trip
    the fp16-stored rows EXACTLY), updates travel int8+scales and land
    (dequantized) on the store."""
    h = _mk_holder(row_dtype)
    svc = _svc(h)
    try:
        legacy = PsClient(svc.addr, wire_codec="off")
        codec = PsClient(svc.addr, wire_codec="fp16+int8")
        signs = np.arange(1, 129, dtype=np.uint64)
        a = legacy.lookup(signs, DIM, True)
        b = codec.lookup(signs, DIM, True)
        assert codec.client.codec_active() is True
        if row_dtype == "fp16":
            # fp16-stored rows survive the fp16 wire bit-exactly
            np.testing.assert_array_equal(a, b)
        else:
            rel = np.abs(a - b) / np.maximum(np.abs(a), 1e-12)
            assert rel.max() <= NARROW_REL["fp16"]
        before = legacy.lookup(signs, DIM, False).copy()
        g = np.full((len(signs), DIM), 0.5, np.float32)
        codec.update_gradients(signs, g, DIM)
        after = legacy.lookup(signs, DIM, False)
        # adagrad step of a 0.5-per-element gradient actually moved rows
        assert np.abs(after - before).max() > 1e-3
        # future paths speak the same codec (fp16-exact only when the
        # STORE is fp16; fp32 rows narrow once on the wire)
        fut = codec.lookup_future(signs, DIM, False)
        if row_dtype == "fp16":
            np.testing.assert_array_equal(fut(), after)
        else:
            rel = np.abs(fut() - after) / np.maximum(np.abs(after), 1e-12)
            assert rel.max() <= NARROW_REL["fp16"]
        codec.update_gradients_future(signs, g, DIM)()
    finally:
        svc.stop()


def test_block_compression_negotiated_roundtrip(monkeypatch):
    """Large frames block-compress (zlib fallback here) once BOTH peers
    negotiated ``__codec__`` — forced on loopback via the env lever —
    and the payload round-trips bit-exactly. A legacy client on the
    same server never sees the flag."""
    import persia_tpu.rpc as rpc

    monkeypatch.setattr(rpc, "_FORCE_BLOCK", True)
    srv = rpc.RpcServer()
    srv.register("echo", lambda p: bytes(p))
    srv.serve_background()
    try:
        payload = b"c" * (rpc.BLOCK_THRESHOLD * 2)  # compressible
        c = rpc.RpcClient(srv.addr, enable_codec=True)
        assert c.call("echo", payload) == payload
        assert c.codec_active() is True
        assert c._conn().block == "zlib"
        legacy = rpc.RpcClient(srv.addr)  # codec off: raw frames
        assert legacy.call("echo", payload) == payload
        assert legacy._conn().block is None
    finally:
        srv.stop()


# --------------------------------------------------------------------------
# checkpoint v2 + incremental packets
# --------------------------------------------------------------------------


def _fill(h, n=200):
    signs = np.arange(1, n + 1, dtype=np.uint64)
    h.lookup(signs, DIM, True)
    h.update_gradients(signs, np.full((n, DIM), 0.1, np.float32), DIM)
    return signs


def test_psd_v2_roundtrip_and_cross_version_compat(tmp_path):
    half = _mk_holder("fp16")
    signs = _fill(half)
    blob = half.dump_bytes()
    version, count = struct.unpack_from("<IQ", blob, 4)
    assert blob[:4] == DUMP_MAGIC and version == 2 and count == len(signs)
    # v2 -> fresh fp16 holder: bit-exact
    h2 = _mk_holder("fp16")
    h2.load_bytes(blob)
    np.testing.assert_array_equal(h2.lookup(signs, DIM, False),
                                  half.lookup(signs, DIM, False))
    # v2 -> fp32 holder (forward compat): widened values
    h32 = _mk_holder("fp32")
    h32.load_bytes(blob)
    np.testing.assert_array_equal(h32.lookup(signs, DIM, False),
                                  half.lookup(signs, DIM, False))
    # fp32 dumps stay v1 (legacy readers), and v1 loads into fp16
    blob32 = h32.dump_bytes()
    assert struct.unpack_from("<IQ", blob32, 4)[0] == 1
    h3 = _mk_holder("fp16")
    h3.load_bytes(blob32)
    rel = np.abs(h3.lookup(signs, DIM, False)
                 - h32.lookup(signs, DIM, False))
    assert rel.max() <= NARROW_REL["fp16"] * np.abs(
        h32.lookup(signs, DIM, False)).max()
    # the streaming reader handles v2
    from persia_tpu.checkpoint import iter_psd_entries

    p = tmp_path / "half.psd"
    half.dump_file(str(p))
    seen = {s: vec for s, d, vec in
            ((s, d, v) for s, d, v in iter_psd_entries(str(p)))}
    assert len(seen) == len(signs)
    for s in signs[:8]:
        np.testing.assert_array_equal(seen[int(s)],
                                      half.get_entry(int(s))[1])


def test_psd_v2_loads_into_native_holder(tmp_path):
    """fp16-train -> native-fp32-serve checkpoint handoff: the C++
    loader only speaks v1, so the native wrapper must decode v2
    record-by-record (widen + set_entry)."""
    from persia_tpu.ps.native import NativeEmbeddingHolder, load_native_lib

    if load_native_lib(build_if_missing=False) is None:
        pytest.skip("native library not built")

    half = _mk_holder("fp16")
    signs = _fill(half, 64)
    p = tmp_path / "half.psd"
    half.dump_file(str(p))
    cc = NativeEmbeddingHolder(100_000, 4)
    cc.configure("bounded_uniform", {"lower": -0.01, "upper": 0.01})
    cc.register_optimizer(dict(ADAGRAD))
    cc.load_file(str(p))
    assert len(cc) == len(signs)
    np.testing.assert_array_equal(cc.lookup(signs, DIM, False),
                                  half.lookup(signs, DIM, False))


def test_inc_update_packets_fp16(tmp_path):
    """A half holder's incremental packets carry v2 (fp16) records and
    replay exactly into an infer-side fp32 holder."""
    from persia_tpu.inc_update import (
        IncrementalUpdateDumper,
        IncrementalUpdateLoader,
    )

    train = _mk_holder("fp16")
    signs = _fill(train, 64)
    dumper = IncrementalUpdateDumper(train, str(tmp_path), buffer_size=10)
    dumper.commit(signs)  # >= buffer_size: flushes a packet
    infer = _mk_holder("fp32")
    loaded = IncrementalUpdateLoader(infer, str(tmp_path)).scan_once()
    assert loaded == len(signs)
    np.testing.assert_array_equal(infer.lookup(signs, DIM, False),
                                  train.lookup(signs, DIM, False))


# --------------------------------------------------------------------------
# int8 error-feedback convergence smoke (real worker/PS path)
# --------------------------------------------------------------------------


def test_int8_ef_convergence_smoke():
    """Embedding regression through the REAL worker->PS RPC path: SGD
    pulls rows toward per-sign targets. The int8+EF wire must land
    within a small factor of the fp32 wire's final loss — error
    feedback is what makes the quantization bias cancel across steps
    (DLRM-small analogue: pooled embedding slots, dense tower elided so
    the assertion isolates the sparse tier)."""
    from persia_tpu.config import EmbeddingSchema, uniform_slots
    from persia_tpu.data.batch import IDTypeFeatureWithSingleID
    from persia_tpu.worker.worker import EmbeddingWorker

    schema = EmbeddingSchema(slots_config=uniform_slots(
        ["slot_0", "slot_1"], dim=DIM))
    rng = np.random.default_rng(3)
    signs = {f"slot_{i}": rng.integers(1, 1 << 40, size=64,
                                       dtype=np.uint64) for i in range(2)}
    targets = {k: rng.normal(scale=0.5, size=(64, DIM)).astype(np.float32)
               for k in signs}

    def run(codec):
        holder = _mk_holder("fp16", SGD, shards=2)
        svc = _svc(holder)
        try:
            client = PsClient(svc.addr, wire_codec=codec)
            worker = EmbeddingWorker(schema, [client])
            worker.configure_parameter_servers(
                "bounded_uniform", {"lower": -0.01, "upper": 0.01},
                1.0, 10.0)
            worker.register_optimizer(dict(SGD))
            loss = None
            for _ in range(30):
                feats = [IDTypeFeatureWithSingleID(k, signs[k])
                         for k in signs]
                ref = worker.put_batch(feats)
                lk = worker.lookup(ref)
                grads = {}
                loss = 0.0
                for k in signs:
                    diff = lk[k].embeddings - targets[k]
                    loss += float((diff ** 2).mean())
                    grads[k] = 2.0 * diff
                worker.update_gradients(ref, grads)
            worker.close()
            return loss
        finally:
            svc.stop()

    fp32_loss = run("off")
    int8_loss = run("fp16+int8")
    # both converged far below the initial ~2*0.25 loss, and the
    # quantized run tracks the fp32 one
    assert fp32_loss < 0.02
    assert int8_loss < max(2.0 * fp32_loss, 0.02)


def test_grad_error_feedback_semantics():
    ef = GradErrorFeedback(capacity_rows=4)
    signs = np.array([1, 2, 1], np.uint64)  # duplicate sign 1
    resid = np.arange(9, dtype=np.float32).reshape(3, 3)
    ef.store(signs, resid, 3)
    assert len(ef) == 2  # duplicate collapsed, LAST occurrence kept
    g = np.zeros((3, 3), np.float32)
    ef.apply(signs, g, 3)
    # sign 1's residual (the last-stored row [6,7,8]) lands on the FIRST
    # occurrence only; consumed afterwards
    np.testing.assert_array_equal(g[0], resid[2])
    np.testing.assert_array_equal(g[1], resid[1])
    np.testing.assert_array_equal(g[2], 0)
    assert len(ef) == 0
    g2 = np.zeros((3, 3), np.float32)
    ef.apply(signs, g2, 3)
    assert not g2.any()
    # capacity bound evicts oldest
    many = np.arange(10, dtype=np.uint64)
    ef.store(many, np.ones((10, 3), np.float32), 3)
    assert len(ef) == 4


# --------------------------------------------------------------------------
# byte-accounted capacity + observability + lint
# --------------------------------------------------------------------------


def test_byte_capacity_admits_2x_rows_at_fp16():
    byte_budget = 100 * DIM * 4  # 100 fp32 rows' worth of emb bytes
    rows = {}
    for rd in ("fp32", "fp16"):
        h = _mk_holder(rd, SGD, capacity=10 ** 9, shards=1,
                       capacity_bytes=byte_budget)
        h.lookup(np.arange(1, 1001, dtype=np.uint64), DIM, True)
        rows[rd] = len(h)
        assert h.resident_bytes <= byte_budget
    assert rows["fp32"] == 100
    assert rows["fp16"] == 200


def test_eviction_map_byte_accounting_exact():
    m = EvictionMap(capacity=10, byte_capacity=None, emb_itemsize=4)
    m.insert(1, 4, np.zeros(8, np.float32))
    assert m.resident_bytes == 32 and m.emb_bytes == 16
    m.insert(1, 4, np.zeros(4, np.float32))  # replace shrinks
    assert m.resident_bytes == 16 and m.emb_bytes == 16
    m.clear()
    assert m.resident_bytes == 0 and m.emb_bytes == 0


def test_health_reports_resident_bytes_and_row_dtype():
    h = _mk_holder("fp16")
    svc = _svc(h)
    try:
        c = PsClient(svc.addr)
        _fill(h, 50)
        doc = c.health()
        assert doc["row_dtype"] == "fp16"
        assert doc["resident_emb_bytes"] == 50 * DIM * 2
        assert doc["resident_bytes"] == 50 * (DIM * 2 + DIM * 4)
        # per-shard gauges refresh on health reads
        from persia_tpu.metrics import default_registry

        rendered = default_registry().render()
        assert "ps_resident_bytes" in rendered
    finally:
        svc.stop()


@pytest.mark.parametrize("backend", ["arena", "native"])
def test_fp16_stack_cuts_wire_and_resident_bytes(backend):
    """The policy's byte budgets over a live PS service, for the Python
    arena and the native store alike: identical lookup+update cycles
    through an fp32 / codec-off stack and an fp16 / fp16+int8 stack
    admit the same rows, put >= 1.4x fewer payload bytes on the wire,
    hold >= 1.8x fewer embedding bytes resident, and read back within
    the documented int8-wire parity budget."""
    from persia_tpu.ps.native import make_holder

    def stack(row_dtype, codec):
        # "native" builds the library on demand and raises without it:
        # a machine that cannot build it fails this case, never skips it
        h = make_holder(100_000, 4, row_dtype=row_dtype, backend=backend)
        h.configure("bounded_uniform", {"lower": -0.01, "upper": 0.01})
        h.register_optimizer(dict(ADAGRAD))
        svc = _svc(h)
        return svc, PsClient(svc.addr, wire_codec=codec)

    (svc_a, full), (svc_b, half) = stack("fp32", "off"), stack(
        "fp16", "fp16+int8")
    try:
        rng = np.random.default_rng(0)
        work = [(rng.integers(1, 1 << 40, 1024, dtype=np.uint64),
                 rng.normal(size=(1024, 4 * DIM)).astype(np.float32))
                for _ in range(4)]
        for c in (full, half):
            c.lookup(work[0][0], 4 * DIM, True)  # dial + negotiate
        sent0 = {c: sum(c.wire_stats().values()) for c in (full, half)}
        for c in (full, half):
            for signs, grads in work:
                c.lookup(signs, 4 * DIM, True)
                c.update_gradients(signs, grads, 4 * DIM)
        wire = {c: sum(c.wire_stats().values()) - sent0[c]
                for c in (full, half)}
        docs = {c: c.health() for c in (full, half)}
        assert docs[full]["holder_entries"] == docs[half]["holder_entries"]
        assert wire[full] / wire[half] >= 1.4, wire
        assert (docs[full]["resident_emb_bytes"]
                / docs[half]["resident_emb_bytes"]) >= 1.8
        a = full.lookup(work[0][0], 4 * DIM, False)
        b = half.lookup(work[0][0], 4 * DIM, False)
        assert np.abs(a - b).max() / np.abs(a).max() <= 2e-1
    finally:
        svc_a.stop()
        svc_b.stop()


def test_old_native_so_negotiates_down_loudly(monkeypatch):
    """An OLD pre-arena ``.so`` (no ptps_new2 and friends) asked for a
    policy it cannot store must negotiate DOWN to the Python arena
    holder with a loud warning — never a silent policy downgrade. A
    hard ``PERSIA_PS_BACKEND=native`` pin raises instead."""
    from persia_tpu.ps import native
    from persia_tpu.ps.arena import ArenaEmbeddingHolder

    class OldLib:  # exports only the pre-arena symbols
        pass

    warnings = []  # the module logger does not propagate; capture direct
    monkeypatch.setattr(native._logger, "warning",
                        lambda msg, *a: warnings.append(msg % a if a
                                                        else msg))
    monkeypatch.setattr(native, "load_native_lib",
                        lambda build_if_missing=True: OldLib())
    assert native.native_capabilities(OldLib()) == frozenset()
    h = native.make_holder(1000, 2, row_dtype="fp16")
    assert isinstance(h, ArenaEmbeddingHolder)
    assert h.row_dtype == "fp16"  # the policy is honored, not dropped
    assert any("negotiating down" in w for w in warnings)
    # byte budgets and the spill tier negotiate the same way
    h2 = native.make_holder(1000, 2, capacity_bytes=1 << 20)
    assert isinstance(h2, ArenaEmbeddingHolder)
    assert sum("negotiating down" in w for w in warnings) == 2
    # a hard native pin fails loudly instead of downgrading
    monkeypatch.setenv("PERSIA_PS_BACKEND", "native")
    with pytest.raises(RuntimeError, match="lacks"):
        native.make_holder(1000, 2, row_dtype="fp16")
    # backend levers: the Python holders are directly addressable
    monkeypatch.setenv("PERSIA_PS_BACKEND", "python-legacy")
    from persia_tpu.ps.store import EmbeddingHolder

    h3 = native.make_holder(1000, 2, row_dtype="fp16")
    assert isinstance(h3, EmbeddingHolder) and h3.row_dtype == "fp16"
    monkeypatch.setenv("PERSIA_PS_BACKEND", "arena")
    assert isinstance(native.make_holder(1000, 2),
                      ArenaEmbeddingHolder)


def test_global_config_parses_row_dtype():
    from persia_tpu.config import GlobalConfig

    gc = GlobalConfig.from_dict({"embedding_parameter_server_config": {
        "row_dtype": "fp16", "capacity_bytes": 1 << 20}})
    assert gc.parameter_server.row_dtype == "fp16"
    assert gc.parameter_server.capacity_bytes == 1 << 20
    assert GlobalConfig.from_dict({}).parameter_server.row_dtype == "fp32"


# --------------------------------------------------------------------------
# memory budget (slow: measures RSS)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_memory_budget_rss_matches_prediction():
    """Fill N rows under fp32 and fp16 and check the RSS DELTA between
    the two matches the predicted per-row data saving (differential
    measurement cancels the fixed per-entry overhead: ndarray header,
    dict slot, LRU links)."""
    import gc
    import os

    def rss():
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")

    n, dim = 300_000, 32
    signs = np.arange(1, n + 1, dtype=np.uint64)
    grown = {}
    holders = []  # keep alive so deltas don't overlap
    for rd in ("fp32", "fp16"):
        h = _mk_holder(rd, SGD, capacity=2 * n, shards=8)
        gc.collect()
        r0 = rss()
        h.lookup(signs, dim, True)
        gc.collect()
        grown[rd] = rss() - r0
        assert h.row_nbytes(dim) == dim * (4 if rd == "fp32" else 2)
        holders.append(h)
    saved = grown["fp32"] - grown["fp16"]
    predicted = n * dim * 2  # fp16 halves the emb slice; sgd has no state
    assert 0.5 * predicted <= saved <= 1.5 * predicted, (
        f"RSS saving {saved / 1e6:.1f} MB vs predicted "
        f"{predicted / 1e6:.1f} MB")
