"""Parity tests: the C++ store must behave identically to the numpy store.

The deterministic init RNG spec (ps/rng.py = native/src/hashrng.h) makes
bit-identical initialization possible; optimizer math may differ by f32
rounding order, so updates compare with a tight tolerance.
"""

import numpy as np
import pytest

from persia_tpu.ps.store import EmbeddingHolder

native = pytest.importorskip("persia_tpu.ps.native")

if native.load_native_lib() is None:
    pytest.skip("native library unavailable", allow_module_level=True)

from persia_tpu.ps.native import NativeEmbeddingHolder


def _pair(optimizer=None, admit=1.0, init=("bounded_uniform", {"lower": -0.1, "upper": 0.1})):
    optimizer = optimizer or {"type": "sgd", "lr": 0.1, "wd": 0.0}
    holders = []
    for cls in (EmbeddingHolder, NativeEmbeddingHolder):
        h = cls(capacity=10_000, num_internal_shards=4)
        h.configure(init[0], init[1], admit_probability=admit, weight_bound=10.0)
        h.register_optimizer(optimizer)
        holders.append(h)
    return holders


def test_farmhash_parity():
    import ctypes

    from persia_tpu.hashing import farmhash64_np

    lib = native.load_native_lib()
    signs = np.random.default_rng(1).integers(0, 2**63, 1000, dtype=np.uint64)
    out = np.empty_like(signs)
    lib.ptps_farmhash64_batch(
        signs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), len(signs),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    np.testing.assert_array_equal(out, farmhash64_np(signs))


@pytest.mark.parametrize("method,params", [
    ("bounded_uniform", {"lower": -0.05, "upper": 0.05}),
    ("normal", {"mean": 0.0, "standard_deviation": 0.02}),
    ("bounded_gamma", {"shape": 2.0, "scale": 0.5}),
    ("bounded_poisson", {"lambda": 3.0}),
    ("zero", {}),
])
def test_init_parity(method, params):
    py, cc = _pair(init=(method, params))
    signs = np.random.default_rng(2).integers(0, 2**63, 64, dtype=np.uint64)
    a = py.lookup(signs, dim=9, training=True)
    b = cc.lookup(signs, dim=9, training=True)
    if method in ("bounded_uniform", "zero", "bounded_poisson"):
        np.testing.assert_array_equal(a, b)  # exact integer/linear math
    else:
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_admit_probability_parity():
    py, cc = _pair(admit=0.3)
    signs = np.arange(1, 5001, dtype=np.uint64)
    py.lookup(signs, 2, True)
    cc.lookup(signs, 2, True)
    assert len(py) == len(cc)
    # same signs admitted
    for s in signs[:500]:
        assert (py.get_entry(int(s)) is None) == (cc.get_entry(int(s)) is None)


@pytest.mark.parametrize("optimizer", [
    {"type": "sgd", "lr": 0.1, "wd": 0.01},
    {"type": "adagrad", "lr": 0.01},
    {"type": "adagrad", "lr": 0.01, "vectorwise_shared": True},
    {"type": "adam", "lr": 0.001},
])
def test_train_loop_parity(optimizer):
    py, cc = _pair(optimizer=optimizer)
    rng = np.random.default_rng(3)
    signs = rng.integers(0, 2**63, 32, dtype=np.uint64)
    dim = 8
    for step in range(5):
        a = py.lookup(signs, dim, True)
        b = cc.lookup(signs, dim, True)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                   err_msg=f"step {step} lookup diverged")
        grads = rng.normal(size=(32, dim)).astype(np.float32)
        py.update_gradients(signs, grads, dim)
        cc.update_gradients(signs, grads.copy(), dim)
    for s in signs:
        pd, pv = py.get_entry(int(s))
        cd, cv = cc.get_entry(int(s))
        assert pd == cd
        np.testing.assert_allclose(pv, cv, rtol=2e-5, atol=1e-6)


def test_dump_format_cross_backend():
    py, cc = _pair()
    signs = np.array([10, 20, 30], dtype=np.uint64)
    py.lookup(signs, 4, True)
    cc.lookup(signs, 4, True)
    import tempfile, os
    with tempfile.TemporaryDirectory() as td:
        py_path = os.path.join(td, "py.psd")
        cc_path = os.path.join(td, "cc.psd")
        py.dump_file(py_path)
        cc.dump_file(cc_path)
        # cross-load: python dump into native store and vice versa
        cc2 = NativeEmbeddingHolder(100, 2)
        cc2.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
        cc2.register_optimizer({"type": "sgd", "lr": 0.1})
        cc2.load_file(py_path)
        assert len(cc2) == 3
        py2 = EmbeddingHolder(100, 2)
        py2.load_file(cc_path)
        assert len(py2) == 3
        for s in signs:
            np.testing.assert_array_equal(py2.get_entry(int(s))[1],
                                          cc2.get_entry(int(s))[1])


def test_native_lru_eviction():
    cc = NativeEmbeddingHolder(capacity=8, num_internal_shards=2)
    cc.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
    cc.register_optimizer({"type": "sgd", "lr": 0.1})
    cc.lookup(np.arange(100, dtype=np.uint64), 2, True)
    assert len(cc) == 8


def test_native_eviction_drops_cold_rows_and_keeps_recent_ones():
    """Pushed 20 % past capacity, the store stays at capacity, rows not
    touched since the fill are gone (an eval lookup zero-fills them) and
    recently updated rows keep their values."""
    capacity, dim = 8192, 4
    cc = NativeEmbeddingHolder(capacity=capacity, num_internal_shards=4)
    cc.configure("bounded_uniform", {"lower": -0.01, "upper": 0.01})
    cc.register_optimizer({"type": "sgd", "lr": 0.1})
    cc.lookup(np.arange(1, capacity + 1, dtype=np.uint64), dim, True)
    cold = np.arange(1, 513, dtype=np.uint64)
    recent = np.arange(capacity - 511, capacity + 1, dtype=np.uint64)
    cc.update_gradients(recent, np.full((512, dim), 5.0, np.float32), dim)
    before = cc.lookup(recent, dim, False).copy()
    cc.lookup(np.arange(capacity + 1, capacity + 1 + capacity // 5,
                        dtype=np.uint64), dim, True)
    assert len(cc) <= capacity
    assert (cc.lookup(cold, dim, False) == 0).all()
    np.testing.assert_array_equal(cc.lookup(recent, dim, False), before)


def test_native_update_missing_sign_counts():
    _, cc = _pair()
    cc.lookup(np.array([1], dtype=np.uint64), 4, True)
    cc.update_gradients(np.array([1, 999], dtype=np.uint64),
                        np.ones((2, 4), np.float32), 4)
    assert cc.gradient_id_miss_count == 1


def test_native_adagrad_reference_golden():
    """The reference optimizer goldens (optim.rs:309-446) replayed through
    the C++ store: seed an entry with the golden initial embedding, apply
    the three golden gradient steps, compare the final entry."""
    from tests.test_sparse_optim import DIM, GRADS, INIT_EMB

    cc = NativeEmbeddingHolder(capacity=100, num_internal_shards=1)
    cc.configure("zero", {})
    cc.register_optimizer({
        "type": "adagrad", "lr": 0.01, "wd": 0.0, "g_square_momentum": 1.0,
        "initialization": 0.01, "eps": 1e-10, "vectorwise_shared": False,
    })
    sign = 42
    vec = np.zeros(DIM * 2, np.float32)
    vec[:DIM] = INIT_EMB
    vec[DIM:] = 0.01  # adagrad state init
    cc.set_entry(sign, DIM, vec)
    for g in GRADS:
        cc.update_gradients(np.array([sign], np.uint64),
                            np.array([g], np.float32), DIM)
    got = cc.get_entry(sign)[1]
    expected = np.array([
        0.6598564, -0.036559787, 0.04014046, 0.34159237, -0.053671654,
        0.6320387, 0.1387946, 0.6141905, 0.47925496, -0.06816861, 0.7330182,
        0.81526995,
        0.6283042, 1.9333843, 1.1247585, 1.496624, 1.2661879, 0.7348535,
        0.021523468, 1.1812702, 1.7385421, 1.073696, 0.13055718, 0.6626925,
    ], np.float32)
    np.testing.assert_allclose(got[:DIM], expected[:DIM], rtol=0, atol=5e-4)
    np.testing.assert_allclose(got[DIM:], expected[DIM:], rtol=1e-6)


def test_stress_parity_under_eviction_and_duplicates():
    """Random batches with duplicate signs and constant eviction
    pressure: both backends must stay value-identical (sequential
    duplicate updates, interleaved init/eviction)."""
    rng = np.random.default_rng(7)
    py = EmbeddingHolder(capacity=64, num_internal_shards=2)
    cc = NativeEmbeddingHolder(capacity=64, num_internal_shards=2)
    for h in (py, cc):
        h.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
        h.register_optimizer({"type": "sgd", "lr": 0.1})
    for step in range(100):
        n = int(rng.integers(1, 40))
        signs = rng.integers(0, 200, n, dtype=np.uint64)
        a = py.lookup(signs, 4, True)
        b = cc.lookup(signs, 4, True)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                   err_msg=f"step {step}")
        g = rng.normal(size=(n, 4)).astype(np.float32)
        py.update_gradients(signs, g, 4)
        cc.update_gradients(signs, g.copy(), 4)
        assert len(py) == len(cc)
    for s in range(200):
        pe, ce = py.get_entry(s), cc.get_entry(s)
        assert (pe is None) == (ce is None)
        if pe is not None:
            np.testing.assert_allclose(pe[1], ce[1], rtol=2e-4, atol=1e-6)


def test_flat_table_rehash_growth_and_eviction():
    """Push one shard well past the initial 1024-slot table (multiple
    rehashes), then through eviction + backward-shift deletions, and
    verify contents against the numpy store."""
    cap = 3000
    py = EmbeddingHolder(capacity=cap, num_internal_shards=1)
    cc = NativeEmbeddingHolder(capacity=cap, num_internal_shards=1)
    for h in (py, cc):
        h.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
        h.register_optimizer({"type": "sgd", "lr": 0.1})
    # phase 1: grow to 5000 inserts -> several rehashes + 2000 evictions
    signs = np.arange(1, 5001, dtype=np.uint64)
    for start in range(0, 5000, 500):
        batch = signs[start : start + 500]
        np.testing.assert_array_equal(py.lookup(batch, 4, True),
                                      cc.lookup(batch, 4, True))
    assert len(py) == cap and len(cc) == cap
    # phase 2: random re-lookups refresh recency identically
    rng = np.random.default_rng(0)
    probe = rng.choice(signs, 2000, replace=False).astype(np.uint64)
    np.testing.assert_array_equal(py.lookup(probe, 4, True),
                                  cc.lookup(probe, 4, True))
    assert len(py) == len(cc) == cap
    # phase 3: exact same survivor set after all the churn
    for s in range(1, 5001, 7):
        assert (py.get_entry(s) is None) == (cc.get_entry(s) is None), s
    # dumps agree entry-for-entry (order may differ across backends only
    # by shard iteration, and there is a single shard here)
    import tempfile, os
    with tempfile.TemporaryDirectory() as td:
        pp, cp = os.path.join(td, "p.psd"), os.path.join(td, "c.psd")
        py.dump_file(pp)
        cc.dump_file(cp)
        from persia_tpu.checkpoint import iter_psd_entries
        pe = {s: v.tobytes() for s, d, v in iter_psd_entries(pp)}
        ce = {s: v.tobytes() for s, d, v in iter_psd_entries(cp)}
        assert pe == ce


# --- arena-era parity: fp16/bf16 rows, byte budgets, PSD v2 ---------------
# The native store shares the arena record layout ([emb bytes | f32
# state], numpy-bit-compatible round-to-nearest-even narrowing) with
# the Python backends, so STORED bytes — not just values — must agree.


def _mk(cls, row_dtype, capacity=10_000, shards=4, optimizer=None, **kw):
    h = cls(capacity=capacity, num_internal_shards=shards,
            row_dtype=row_dtype, **kw)
    h.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1},
                admit_probability=1.0, weight_bound=10.0)
    h.register_optimizer(optimizer or {"type": "adagrad", "lr": 0.01})
    return h


def test_native_capabilities_are_arena_era():
    from persia_tpu.ps.native import native_capabilities

    caps = native_capabilities()
    assert {"row_dtype", "capacity_bytes", "psd_v2", "spill"} <= caps


@pytest.mark.parametrize("row_dtype", ["fp16", "bf16"])
def test_half_row_init_lookup_bit_parity(row_dtype):
    """Fresh-init lookups return narrow-then-widened STORED values;
    with the deterministic init RNG and bit-compatible narrowing they
    must be bit-identical across all three backends."""
    from persia_tpu.ps.arena import ArenaEmbeddingHolder

    py = _mk(EmbeddingHolder, row_dtype)
    ar = _mk(ArenaEmbeddingHolder, row_dtype)
    cc = _mk(NativeEmbeddingHolder, row_dtype)
    signs = np.random.default_rng(11).integers(0, 2**63, 128,
                                               dtype=np.uint64)
    a = py.lookup(signs, 9, True)
    b = ar.lookup(signs, 9, True)
    c = cc.lookup(signs, 9, True)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)
    # immediate re-read returns the same stored bytes
    np.testing.assert_array_equal(c, cc.lookup(signs, 9, True))
    assert py.resident_bytes == ar.resident_bytes == cc.resident_bytes
    assert (py.resident_emb_bytes == ar.resident_emb_bytes
            == cc.resident_emb_bytes == 128 * 9 * 2)


@pytest.mark.parametrize("row_dtype", ["fp16", "bf16"])
@pytest.mark.parametrize("optimizer", [
    {"type": "sgd", "lr": 0.1, "wd": 0.01},
    {"type": "adagrad", "lr": 0.01},
    {"type": "adam", "lr": 0.001},
])
def test_half_row_train_loop_parity(row_dtype, optimizer):
    py = _mk(EmbeddingHolder, row_dtype, optimizer=optimizer)
    cc = _mk(NativeEmbeddingHolder, row_dtype, optimizer=optimizer)
    rng = np.random.default_rng(3)
    signs = rng.integers(0, 2**63, 32, dtype=np.uint64)
    dim = 8
    for step in range(5):
        a = py.lookup(signs, dim, True)
        b = cc.lookup(signs, dim, True)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                   err_msg=f"step {step} lookup diverged")
        grads = rng.normal(size=(32, dim)).astype(np.float32)
        py.update_gradients(signs, grads, dim)
        cc.update_gradients(signs, grads.copy(), dim)
    for s in signs:
        pd, pv = py.get_entry(int(s))
        cd, cv = cc.get_entry(int(s))
        assert pd == cd
        np.testing.assert_allclose(pv, cv, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("row_dtype", ["fp16", "bf16"])
def test_half_row_byte_budget_eviction_parity(row_dtype):
    """Byte-accounted eviction must pick the identical victims on both
    backends (same logical bytes/row, same LRU order)."""
    row = 8 * 2 + 8 * 4  # fp16/bf16 emb + adagrad f32 state at dim 8
    kw = dict(capacity=100_000, shards=2, capacity_bytes=64 * row)
    py = _mk(EmbeddingHolder, row_dtype, **kw)
    cc = _mk(NativeEmbeddingHolder, row_dtype, **kw)
    rng = np.random.default_rng(9)
    for step in range(100):
        n = int(rng.integers(1, 50))
        signs = rng.integers(0, 300, n, dtype=np.uint64)
        a = py.lookup(signs, 8, True)
        b = cc.lookup(signs, 8, True)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                   err_msg=f"step {step}")
        assert len(py) == len(cc)
        assert py.resident_bytes == cc.resident_bytes
    for s in range(300):
        assert (py.get_entry(s) is None) == (cc.get_entry(s) is None), s


@pytest.mark.parametrize("row_dtype", ["fp16", "bf16"])
def test_psd_v2_round_trip_bit_parity_both_directions(row_dtype):
    """python-dump -> native-load -> native-dump must be byte-identical
    with the original (and vice versa): one record layout, one framing,
    narrow bytes preserved exactly through widen/narrow round trips."""
    import os
    import tempfile

    py = _mk(EmbeddingHolder, row_dtype)
    cc = _mk(NativeEmbeddingHolder, row_dtype)
    signs = np.random.default_rng(4).integers(0, 2**63, 300,
                                              dtype=np.uint64)
    py.lookup(signs, 16, True)
    cc.lookup(signs, 16, True)
    with tempfile.TemporaryDirectory() as td:
        pp, cp = os.path.join(td, "p.psd"), os.path.join(td, "c.psd")
        py.dump_file(pp)
        cc.dump_file(cp)
        with open(pp, "rb") as f:
            py_bytes = f.read()
        with open(cp, "rb") as f:
            cc_bytes = f.read()
        assert py_bytes[:8] == b"PSD1" + (2).to_bytes(4, "little")
        assert py_bytes == cc_bytes
        # cross-load, re-dump, compare bytes
        cc2 = _mk(NativeEmbeddingHolder, row_dtype)
        cc2.load_file(pp)
        py2 = _mk(EmbeddingHolder, row_dtype)
        py2.load_file(cp)
        pp2, cp2 = os.path.join(td, "p2.psd"), os.path.join(td, "c2.psd")
        py2.dump_file(pp2)
        cc2.dump_file(cp2)
        with open(pp2, "rb") as f:
            assert f.read() == cc_bytes
        with open(cp2, "rb") as f:
            assert f.read() == py_bytes
        # v2 loads into an fp32 holder of either backend (widen on read)
        wide_py = _mk(EmbeddingHolder, "fp32")
        wide_py.load_file(cp)
        wide_cc = _mk(NativeEmbeddingHolder, "fp32")
        wide_cc.load_file(pp)
        assert len(wide_py) == len(wide_cc) == 300
        for s in signs[:50]:
            np.testing.assert_array_equal(wide_py.get_entry(int(s))[1],
                                          wide_cc.get_entry(int(s))[1])


def test_native_spill_demotion_and_fault_in():
    """The native store's retained-eviction drain feeds the shared
    SpillStore: evictions demote instead of dying, later lookups fault
    rows back in, and a spill-armed checkpoint is ONE logical table —
    parity against the Python arena holder over the same traffic (the
    budget comfortably exceeds one batch: intra-batch churn ordering
    is the documented divergence regime)."""
    import os
    import tempfile

    from persia_tpu.ps.arena import ArenaEmbeddingHolder

    rng = np.random.default_rng(5)
    row = 8 * 2 + 8 * 4
    with tempfile.TemporaryDirectory() as td:
        kw = dict(capacity=100_000, shards=2, capacity_bytes=96 * row)
        ar = _mk(ArenaEmbeddingHolder, "fp16",
                 spill_dir=os.path.join(td, "a"), **kw)
        cc = _mk(NativeEmbeddingHolder, "fp16",
                 spill_dir=os.path.join(td, "c"), **kw)
        for step in range(80):
            n = int(rng.integers(1, 30))
            signs = rng.integers(0, 150, n, dtype=np.uint64)
            a = ar.lookup(signs, 8, True)
            b = cc.lookup(signs, 8, True)
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                       err_msg=f"step {step}")
            g = rng.normal(size=(n, 8)).astype(np.float32)
            ar.update_gradients(signs, g, 8)
            cc.update_gradients(signs, g.copy(), 8)
            assert len(ar) == len(cc), step
        assert cc.spill_stats()["spilled_rows"] > 0
        assert cc.spill_stats()["spill_fault_ins_total"] > 0
        # one logical table: dump the spill-armed native holder, load
        # into a flat python holder, compare every entry
        path = os.path.join(td, "c.psd")
        cc.dump_file(path)
        back = _mk(EmbeddingHolder, "fp16", capacity=100_000, shards=2)
        back.load_file(path)
        assert len(back) == len(cc)
        for s in range(150):
            a, b = back.get_entry(s), cc.get_entry(s)
            assert (a is None) == (b is None), s
            if a is not None:
                assert a[0] == b[0]
                np.testing.assert_array_equal(a[1], b[1])


def test_native_arena_stats_surface():
    cc = _mk(NativeEmbeddingHolder, "fp16", capacity=1000, shards=2)
    signs = np.arange(1, 201, dtype=np.uint64)
    cc.lookup(signs, 8, True)
    stats = cc.arena_stats()
    assert stats["live_rows"] == 200
    assert stats["slab_bytes"] > 0
    assert stats["free_slots"] == 0
    assert stats["fragmentation_ratio"] == 0.0
    assert stats["resident_bytes"] == cc.resident_bytes
    per_shard = cc.resident_bytes_per_shard()
    assert len(per_shard) == 2 and sum(per_shard) == cc.resident_bytes


# --- middleware kernel parity (native/src/mw_kernels.h) -------------------


def test_mw_dedup_matches_numpy_unique():
    from persia_tpu.worker import mw_native

    assert mw_native.available()
    rng = np.random.default_rng(7)
    for n in (0, 1, 17, 4096):
        signs = rng.integers(0, 1000, size=n, dtype=np.uint64)
        d_ref, inv_ref = np.unique(signs, return_inverse=True)
        d_nat, inv_nat = mw_native.dedup(signs)
        np.testing.assert_array_equal(d_nat, d_ref)
        np.testing.assert_array_equal(inv_nat, inv_ref.astype(np.int32))


def test_mw_dedup_radix_branch():
    """> 1024 distinct signs takes the LSD radix path (incl. the
    constant-byte pass skip); cover full-64-bit keys, keys differing only
    in the high bytes, and keys sharing low bytes."""
    from persia_tpu.worker import mw_native

    rng = np.random.default_rng(13)
    cases = [
        rng.integers(0, 1 << 63, size=8000, dtype=np.uint64),  # full range
        # differ ONLY in the top two bytes
        (rng.integers(0, 5000, size=8000, dtype=np.uint64) << np.uint64(48))
        | np.uint64(0xABCD),
        # low 16 bits shared, middle varying
        (rng.integers(0, 3000, size=4096, dtype=np.uint64) << np.uint64(16)),
    ]
    for signs in cases:
        d_ref, inv_ref = np.unique(signs, return_inverse=True)
        assert len(d_ref) > 1024  # must exercise the radix branch
        d_nat, inv_nat = mw_native.dedup(signs)
        np.testing.assert_array_equal(d_nat, d_ref)
        np.testing.assert_array_equal(inv_nat, inv_ref.astype(np.int32))


def test_mw_middleware_bit_parity_full_pipeline():
    """The full middleware pipeline must produce bit-identical outputs
    with and without the C++ kernels (sum + raw + sqrt-scaling +
    hashstack + loss scale)."""
    import os

    from persia_tpu.config import EmbeddingSchema
    from persia_tpu.data.batch import IDTypeFeature
    from persia_tpu.worker import middleware as mw
    from persia_tpu.worker import mw_native

    assert mw_native.available()
    schema = EmbeddingSchema.from_dict({
        "slots_config": {
            "summed": {"dim": 8, "sqrt_scaling": True},
            "raw": {"dim": 4, "embedding_summation": False,
                    "sample_fixed_size": 3},
            "stacked": {"dim": 8, "hash_stack_config": {
                "hash_stack_rounds": 2, "embedding_size": 100}},
        }
    })
    rng = np.random.default_rng(3)
    data_summed = [rng.integers(0, 500, size=rng.integers(0, 6),
                                dtype=np.uint64) for _ in range(32)]
    data_raw = [rng.integers(0, 500, size=rng.integers(0, 8),
                             dtype=np.uint64) for _ in range(32)]
    data_stacked = [rng.integers(0, 100000, size=rng.integers(1, 4),
                                 dtype=np.uint64) for _ in range(32)]

    def run():
        feats = mw.preprocess_batch(
            [IDTypeFeature("summed", data_summed),
             IDTypeFeature("raw", data_raw),
             IDTypeFeature("stacked", data_stacked)], schema)
        embs = [rng2.normal(size=(f.num_distinct,
                                  schema.get_slot(f.name).dim))
                .astype(np.float32) for f in feats]
        outs = [mw.postprocess_feature(f, schema.get_slot(f.name), e)
                for f, e in zip(feats, embs)]
        grads = []
        for o in outs:
            g = rng2.normal(size=o.embeddings.shape).astype(np.float32)
            g.ravel()[::97] = np.nan  # exercise the NaN filter
            grads.append(g)
        aggs = [mw.aggregate_gradients(f, schema.get_slot(f.name), g,
                                       loss_scale=2.5)
                for f, g in zip(feats, grads)]
        return feats, outs, aggs

    rng2 = np.random.default_rng(11)
    f_nat, o_nat, a_nat = run()
    os.environ["PERSIA_FORCE_PYTHON_MW"] = "1"
    mw_native._checked, mw_native._lib = False, None
    try:
        rng2 = np.random.default_rng(11)
        f_py, o_py, a_py = run()
    finally:
        del os.environ["PERSIA_FORCE_PYTHON_MW"]
        mw_native._checked, mw_native._lib = False, None

    for fn, fp in zip(f_nat, f_py):
        np.testing.assert_array_equal(fn.distinct_signs, fp.distinct_signs)
        np.testing.assert_array_equal(fn.elem_distinct, fp.elem_distinct)
    for on, op in zip(o_nat, o_py):
        np.testing.assert_array_equal(on.embeddings, op.embeddings)
        if hasattr(on, "index"):
            np.testing.assert_array_equal(on.index, op.index)
    for an, ap in zip(a_nat, a_py):
        np.testing.assert_array_equal(an, ap)


def test_mw_shard_order_matches_numpy_split():
    from persia_tpu.hashing import sign_to_shard
    from persia_tpu.worker import mw_native

    rng = np.random.default_rng(21)
    for n, replica in ((0, 2), (1, 1), (4096, 2), (4096, 7)):
        signs = rng.integers(0, 1 << 63, size=n, dtype=np.uint64)
        order, starts = mw_native.shard_order(signs, replica)
        shards = sign_to_shard(signs, replica)
        assert int(starts[-1]) == n
        for s in range(replica):
            sel = order[int(starts[s]):int(starts[s + 1])]
            ref = np.nonzero(shards == s)[0]
            np.testing.assert_array_equal(sel, ref.astype(np.int32))


@pytest.mark.slow
def test_parity_under_asan():
    """Re-run this module's parity suite against the sanitizer build
    (`make -C native sanitize`), in a subprocess with the ASan runtime
    preloaded. Skipped when the ASan artifacts or toolchain are absent;
    CI at minimum compiles the target so sanitizer bitrot fails fast."""
    import os
    import shutil
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    asan_so = os.path.join(repo, "native", "build", "asan",
                           "libpersia_native.so")
    if not os.path.exists(asan_so):
        pytest.skip("no ASan build; run `make -C native sanitize`")
    gxx = shutil.which("g++")
    if gxx is None:
        pytest.skip("g++ unavailable to locate the ASan runtime")
    preload = []
    for rt in ("libasan.so", "libubsan.so"):
        p = subprocess.run([gxx, f"-print-file-name={rt}"],
                           capture_output=True, text=True).stdout.strip()
        if not os.path.isabs(p):
            pytest.skip(f"{rt} not found by {gxx}")
        preload.append(p)

    env = dict(os.environ)
    env.update({
        "LD_PRELOAD": " ".join(preload),
        # python itself "leaks" at interpreter exit; halt_on_error stays
        # on for real memory bugs, which is the point of the run
        "ASAN_OPTIONS": "detect_leaks=0",
        "PERSIA_NATIVE_LIB": asan_so,
        "JAX_PLATFORMS": "cpu",
    })
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.abspath(__file__), "-q",
         "-p", "no:cacheprovider", "-k", "not asan"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=900)
    tail = (proc.stdout + proc.stderr)[-4000:]
    assert proc.returncode == 0, f"parity under ASan failed:\n{tail}"
    assert "AddressSanitizer" not in tail, f"sanitizer report:\n{tail}"
