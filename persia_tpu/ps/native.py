"""ctypes binding to the C++ embedding store (native/build/libpersia_native.so).

``NativeEmbeddingHolder`` exposes the same interface as the Python
holders (:class:`persia_tpu.ps.arena.ArenaEmbeddingHolder` and the
legacy per-entry :class:`persia_tpu.ps.store.EmbeddingHolder`);
semantics and serialization (PSD v1/v2) are identical, and the
deterministic init RNG is bit-compatible, so the backends are
interchangeable (tests/test_native_parity.py enforces this — including
fp16/bf16 row storage and byte-accounted eviction, which the native
arena store implements over the SAME record byte layout as the Python
side since PR 10).

Capability negotiation: the arena-era C ABI (``ptps_new2`` + friends)
is probed per loaded library. An OLD ``.so`` (pre-arena) still serves
plain-fp32 row-count-capacity stores; asking it for fp16/bf16 rows, a
byte budget, or the spill tier makes :func:`make_holder` negotiate
DOWN to the Python arena holder with a loud warning (or raise, under
``PERSIA_PS_BACKEND=native``) — never a silent policy downgrade.

The disk spill tier stays implemented once, in Python
(:mod:`persia_tpu.ps.spill`): the native store RETAINS evicted rows in
a drain buffer (``ptps_set_retain_evicted``) and this wrapper demotes
the drained records — the identical logical ``[emb bytes | f32 state]``
byte image the Python holders spill — and faults spilled rows back in
ahead of the native call.

Use :func:`make_holder` to get the right backend for a storage policy
(also steerable via the ``PERSIA_PS_BACKEND`` knob).
"""

import contextlib
import ctypes
import os
import struct
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from persia_tpu import knobs
from persia_tpu.logger import get_default_logger

_logger = get_default_logger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_LIB_CANDIDATES = [
    os.path.join(_REPO_ROOT, "native", "build", "libpersia_native.so"),
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "native_bin",
                 "libpersia_native.so"),
]

_INIT_METHOD_CODES = {
    "bounded_uniform": 0,
    "bounded_gamma": 1,
    "bounded_poisson": 2,
    "normal": 3,
    "truncated_normal": 4,
    "zero": 5,
}

_ROW_DTYPE_CODES = {"fp32": 0, "fp16": 1, "bf16": 2}

# every symbol of the arena-era ABI; all present <=> the .so implements
# row_dtype narrowing, byte-accounted eviction, PSD v2, the eviction
# drain (spill), and the arena stats surface
_ARENA_SYMBOLS = (
    "ptps_new2", "ptps_row_dtype", "ptps_resident_bytes",
    "ptps_resident_emb_bytes", "ptps_shard_resident_bytes",
    "ptps_arena_stats", "ptps_set_retain_evicted", "ptps_evicted_bytes",
    "ptps_drain_evicted", "ptps_contains",
)

# the SIMD-era ABI (a second, independent capability set: an arena-era
# .so without these still serves every storage policy — only the SIMD
# kernels, tunable shard-parallelism, and batched entry calls are
# missing, and the service tier negotiates down to its legacy constants)
_SIMD_SYMBOLS = (
    "ptps_simd_path", "ptps_simd_force", "ptps_narrow_rows",
    "ptps_widen_rows", "ptps_set_parallel", "ptps_get_parallel",
    "ptps_set_entries", "ptps_get_entries",
)

_lib = None


def _build_native() -> bool:
    makefile = os.path.join(_REPO_ROOT, "native", "Makefile")
    if not os.path.exists(makefile):
        return False
    try:
        subprocess.run(
            ["make", "-C", os.path.join(_REPO_ROOT, "native"), "-j", "8"],
            check=True, capture_output=True,
        )
        return True
    except (subprocess.CalledProcessError, OSError) as e:
        _logger.warning("native build failed: %s", e)
        return False


def load_native_lib(build_if_missing: bool = True) -> Optional[ctypes.CDLL]:
    """Load (building on demand) the native library; None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    # explicit override first: the ASan parity hook (and any operator
    # pinning a specific build) names the .so directly. A missing
    # override raises instead of silently falling back to the default
    # candidates — the operator believes a SPECIFIC build is loaded
    override = knobs.get("PERSIA_NATIVE_LIB")
    if override and not os.path.exists(override):
        raise FileNotFoundError(
            f"PERSIA_NATIVE_LIB={override!r} does not exist; unset it "
            "or rebuild (e.g. `make -C native sanitize`)")
    candidates = ([override] if override else []) + _LIB_CANDIDATES
    path = next((p for p in candidates if os.path.exists(p)), None)
    if path is None and build_if_missing and _build_native():
        path = next((p for p in candidates if os.path.exists(p)), None)
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    u64, u32, i32, i64 = (ctypes.c_uint64, ctypes.c_uint32, ctypes.c_int,
                          ctypes.c_int64)
    fptr = ctypes.c_float
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ptps_new.restype = ctypes.c_void_p
    lib.ptps_new.argtypes = [u64, u32]
    lib.ptps_free.argtypes = [ctypes.c_void_p]
    lib.ptps_configure.argtypes = [
        ctypes.c_void_p, i32, ctypes.POINTER(ctypes.c_double), fptr, fptr, i32]
    lib.ptps_register_optimizer.restype = i32
    lib.ptps_register_optimizer.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptps_lookup.restype = i32
    lib.ptps_lookup.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64), u64, u32,
                                i32, ctypes.POINTER(fptr)]
    lib.ptps_update.restype = i32
    lib.ptps_update.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64), u64, u32,
                                ctypes.POINTER(fptr)]
    lib.ptps_len.restype = u64
    lib.ptps_len.argtypes = [ctypes.c_void_p]
    lib.ptps_clear.argtypes = [ctypes.c_void_p]
    lib.ptps_index_miss_count.restype = u64
    lib.ptps_index_miss_count.argtypes = [ctypes.c_void_p]
    lib.ptps_gradient_id_miss_count.restype = u64
    lib.ptps_gradient_id_miss_count.argtypes = [ctypes.c_void_p]
    lib.ptps_get_entry.restype = i64
    lib.ptps_get_entry.argtypes = [ctypes.c_void_p, u64, ctypes.POINTER(fptr),
                                   u32, ctypes.POINTER(u32)]
    lib.ptps_set_entry.restype = i32
    lib.ptps_set_entry.argtypes = [ctypes.c_void_p, u64, u32,
                                   ctypes.POINTER(fptr), u32]
    lib.ptps_dump.restype = i32
    lib.ptps_dump.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptps_load.restype = i32
    lib.ptps_load.argtypes = [ctypes.c_void_p, ctypes.c_char_p, i32]
    lib.ptps_farmhash64.restype = u64
    lib.ptps_farmhash64.argtypes = [u64]
    lib.ptps_farmhash64_batch.argtypes = [ctypes.POINTER(u64), u64,
                                          ctypes.POINTER(u64)]
    lib.ptps_init_entry.argtypes = [u64, u32, i32,
                                    ctypes.POINTER(ctypes.c_double),
                                    ctypes.POINTER(fptr)]
    # arena-era ABI (declared only when the .so exports it — an older
    # library simply lacks the symbols and the capability probe says so)
    if all(hasattr(lib, s) for s in _ARENA_SYMBOLS):
        lib.ptps_new2.restype = ctypes.c_void_p
        lib.ptps_new2.argtypes = [u64, u32, i32, u64]
        lib.ptps_row_dtype.restype = i32
        lib.ptps_row_dtype.argtypes = [ctypes.c_void_p]
        lib.ptps_resident_bytes.restype = u64
        lib.ptps_resident_bytes.argtypes = [ctypes.c_void_p]
        lib.ptps_resident_emb_bytes.restype = u64
        lib.ptps_resident_emb_bytes.argtypes = [ctypes.c_void_p]
        lib.ptps_shard_resident_bytes.argtypes = [ctypes.c_void_p,
                                                  ctypes.POINTER(u64)]
        lib.ptps_arena_stats.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(u64)]
        lib.ptps_set_retain_evicted.argtypes = [ctypes.c_void_p, i32]
        lib.ptps_evicted_bytes.restype = u64
        lib.ptps_evicted_bytes.argtypes = [ctypes.c_void_p]
        lib.ptps_drain_evicted.restype = u64
        lib.ptps_drain_evicted.argtypes = [ctypes.c_void_p, u8p, u64]
        lib.ptps_contains.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64),
                                      u64, u8p]
    # SIMD-era ABI (independent probe: negotiate-down keeps working on a
    # library that predates it)
    if all(hasattr(lib, s) for s in _SIMD_SYMBOLS):
        lib.ptps_simd_path.restype = ctypes.c_char_p
        lib.ptps_simd_path.argtypes = []
        lib.ptps_simd_force.restype = i32
        lib.ptps_simd_force.argtypes = [ctypes.c_char_p]
        lib.ptps_narrow_rows.argtypes = [i32, ctypes.POINTER(fptr), u64, u8p,
                                         i32]
        lib.ptps_widen_rows.argtypes = [i32, u8p, u64, ctypes.POINTER(fptr),
                                        i32]
        lib.ptps_set_parallel.argtypes = [ctypes.c_void_p, u32, u64]
        lib.ptps_get_parallel.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(u64)]
        lib.ptps_set_entries.restype = i32
        lib.ptps_set_entries.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64),
                                         u64, u32, ctypes.POINTER(fptr), u32]
        lib.ptps_get_entries.restype = i64
        lib.ptps_get_entries.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64),
                                         u64, u32, ctypes.POINTER(fptr),
                                         ctypes.POINTER(i64)]
    _lib = lib
    return lib


def native_capabilities(lib=None) -> frozenset:
    """Storage-policy capabilities of the loaded native library. The
    arena-era ABI implements them as one indivisible set; an older
    ``.so`` (plain fp32, row-count eviction, PSD v1) reports empty —
    the make_holder negotiation keys on this, never on versions."""
    if lib is None:
        lib = load_native_lib(build_if_missing=False)
    if lib is None:
        return frozenset()
    caps = set()
    if all(hasattr(lib, s) for s in _ARENA_SYMBOLS):
        caps.update({"row_dtype", "capacity_bytes", "psd_v2",
                     "spill", "arena_stats"})
    if all(hasattr(lib, s) for s in _SIMD_SYMBOLS):
        caps.update({"simd", "parallel_tuning", "batched_entries"})
    return frozenset(caps)


def required_capabilities(row_dtype=None, capacity_bytes=None,
                          spill_dir=None) -> frozenset:
    """The native capabilities a storage policy needs (empty = any
    ``.so`` ever shipped can serve it)."""
    need = set()
    if row_dtype not in (None, "fp32"):
        need.update({"row_dtype", "psd_v2"})
    if capacity_bytes:
        need.add("capacity_bytes")
    if spill_dir:
        need.add("spill")
    return frozenset(need)


def _f32_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _u64_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def _u8_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i64_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def native_simd_path(lib=None) -> Optional[str]:
    """Kernel path the loaded native library selected ("avx2" | "neon" |
    "scalar"), honoring the PERSIA_NATIVE_SIMD knob; None when no
    library is loaded or it predates the SIMD ABI."""
    if lib is None:
        lib = load_native_lib(build_if_missing=False)
    if lib is None or "simd" not in native_capabilities(lib):
        return None
    return lib.ptps_simd_path().decode()


def _params_array(params: dict):
    vals = [params.get("lower", -0.01), params.get("upper", 0.01),
            params.get("mean", 0.0), params.get("standard_deviation", 0.01),
            params.get("shape", 1.0), params.get("scale", 1.0),
            params.get("lambda", 1.0)]
    return (ctypes.c_double * 7)(*vals)


def optimizer_config_to_wire(config: dict, feature_index_prefix_bit: int = 0) -> str:
    """Serialize an optimizer config dict to the native wire string
    (parsed by OptimizerConfig::parse in native/src/optim.h)."""
    kind = config["type"]
    if kind == "sgd":
        return f"sgd {config['lr']} {config.get('wd', 0.0)}"
    if kind == "adagrad":
        return (
            f"adagrad {config.get('lr', 1e-2)} {config.get('wd', 0.0)} "
            f"{config.get('g_square_momentum', 1.0)} "
            f"{config.get('initialization', 1e-2)} {config.get('eps', 1e-10)} "
            f"{1 if config.get('vectorwise_shared', False) else 0}"
        )
    if kind == "adam":
        return (
            f"adam {config.get('lr', 1e-3)} {config.get('beta1', 0.9)} "
            f"{config.get('beta2', 0.999)} {config.get('eps', 1e-8)} "
            f"{feature_index_prefix_bit}"
        )
    raise ValueError(f"unknown optimizer type {kind!r}")


# spill/drain record framing: sign u64 | dim u32 | stored nbytes u32
_DRAIN_REC = struct.Struct("<QII")


class NativeEmbeddingHolder:
    """Drop-in replacement for the Python holders backed by the C++
    arena store. ``row_dtype``/``capacity_bytes`` require the arena-era
    library (RuntimeError otherwise — make_holder negotiates down
    instead); ``spill_dir`` arms the shared Python SpillStore fed by
    the store's retained-eviction drain."""

    # ctypes drops the GIL for the duration of every foreign call, so
    # the service tier's shard-parallel dispatch gets real parallelism
    # from one process (ps_service.ShardParallelDispatcher keys on this)
    releases_gil = True

    def __init__(self, capacity: int = 1_000_000_000, num_internal_shards: int = 8,
                 hotness=None, row_dtype: str = "fp32",
                 capacity_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 spill_bytes: Optional[int] = None):
        lib = load_native_lib()
        if lib is None:
            raise RuntimeError(
                "native library not available; run `make -C native` or use "
                "persia_tpu.ps.arena.ArenaEmbeddingHolder"
            )
        row_dtype = row_dtype or "fp32"
        capacity_bytes = capacity_bytes or None
        spill_dir = spill_dir or None
        self._caps = native_capabilities(lib)
        missing = required_capabilities(row_dtype, capacity_bytes,
                                        spill_dir) - self._caps
        if missing:
            raise RuntimeError(
                f"loaded native library lacks {sorted(missing)} needed by "
                f"this storage policy (row_dtype={row_dtype!r}, "
                f"capacity_bytes={capacity_bytes}, spill_dir={spill_dir!r})"
                " — rebuild `make -C native`, or let make_holder negotiate "
                "down to the Python arena holder")
        self._lib = lib
        if self._caps:
            self._h = lib.ptps_new2(capacity, num_internal_shards,
                                    _ROW_DTYPE_CODES[row_dtype],
                                    capacity_bytes or 0)
        else:
            self._h = lib.ptps_new(capacity, num_internal_shards)
        self.capacity = capacity
        self.capacity_bytes = capacity_bytes
        self.num_internal_shards = num_internal_shards
        self.row_dtype = row_dtype
        # LOUD: name the engaged kernel path at init so a replica that
        # silently degraded to scalar (bad knob value, older CPU) is
        # visible in logs — and exported via /healthz + fleet gauges
        self.simd_path = (lib.ptps_simd_path().decode()
                          if "simd" in self._caps else None)
        if self.simd_path is not None:
            _logger.info(
                "native store SIMD kernel path: %s "
                "(PERSIA_NATIVE_SIMD=%s, row_dtype=%s)",
                self.simd_path, knobs.get("PERSIA_NATIVE_SIMD") or "auto",
                row_dtype)
        else:
            _logger.info(
                "native store predates the SIMD ABI: scalar kernels, no "
                "parallel tuning (rebuild `make -C native`)")
        # widen/narrow policy of the logical record bytes (drain + spill)
        from persia_tpu.ps.optim import RowPrecision

        self._rp = RowPrecision(row_dtype)
        # Mirrors the Python holders' optimizer being None until
        # registered: readiness checks (PS _ready -> worker recovery
        # re-arm) must see an unarmed native holder as NOT ready.
        self.optimizer = None
        # workload hotness sketches live in this Python wrapper (the
        # C++ store never sees them): the tracker owns its own leaf
        # locks, so observing before the ctypes call races nothing
        from persia_tpu import hotness as _hotness

        self.hotness = _hotness.make_tracker(num_internal_shards,
                                             enabled=hotness)
        # disk spill tier: shared Python implementation over the same
        # logical record bytes; the store retains evictions for us
        if spill_dir:
            from persia_tpu.ps.spill import SpillStore

            self.spill: Optional["SpillStore"] = SpillStore(
                spill_dir, max_bytes=spill_bytes or None)
            lib.ptps_set_retain_evicted(self._h, 1)
            # SPILL-ARMED CALLS SERIALIZE at the wrapper: the
            # drain -> resident-filter -> SpillStore handoff spans
            # several unlocked steps, and a concurrent training lookup
            # landing in the neither-tier window would silently
            # reinitialize a demoted row. The Python holders demote
            # under their shard locks; this lock is the wrapper's
            # equivalent (the C++ store still shard-parallelizes
            # WITHIN each call, and unarmed holders stay lock-free).
            self._mu: Optional[threading.RLock] = threading.RLock()
        else:
            self.spill = None
            self._mu = None

    def _guard(self):
        return self._mu if self._mu is not None else (
            contextlib.nullcontext())

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.ptps_free(h)
            self._h = None

    def parallel_info(self) -> Optional[dict]:
        """Capability probe for the service-tier dispatcher: the native
        store's resolved shard-parallel worker count and the batch size
        below which it stays serial. None when the loaded ``.so``
        predates tunable parallelism (the dispatcher then falls back to
        its legacy constants — negotiate-down, never a crash)."""
        if "parallel_tuning" not in self._caps:
            return None
        out = np.zeros(2, np.uint64)
        self._lib.ptps_get_parallel(self._h, _u64_ptr(out))
        return {"threads": int(out[0]), "min_batch": int(out[1])}

    def set_parallel(self, threads: int = 0, min_batch: int = 0) -> bool:
        """Tune the native shard-parallel engine (threads=0 restores
        auto; min_batch=0 leaves the serial threshold unchanged).
        Returns False on a pre-SIMD-ABI library."""
        if "parallel_tuning" not in self._caps:
            return False
        self._lib.ptps_set_parallel(self._h, int(threads), int(min_batch))
        return True

    def configure(self, init_method: str, init_params: dict,
                  admit_probability: float = 1.0, weight_bound: float = 10.0,
                  enable_weight_bound: bool = True):
        self._lib.ptps_configure(
            self._h, _INIT_METHOD_CODES[init_method], _params_array(init_params),
            admit_probability, weight_bound, 1 if enable_weight_bound else 0,
        )

    def register_optimizer(self, config: dict, feature_index_prefix_bit: int = 0):
        wire = optimizer_config_to_wire(config, feature_index_prefix_bit)
        if self._lib.ptps_register_optimizer(self._h, wire.encode()) != 0:
            raise ValueError(f"native optimizer rejected config {config}")
        self.optimizer = dict(config)

    # --- spill plumbing ---------------------------------------------------

    def _drain_evictions(self):
        """Demote the store's retained evictions to the disk tier.
        Records carry the logical stored bytes, so the spill round trip
        is bit-identical across backends. A sign that was evicted and
        re-admitted within the same call is filtered out (a resident
        row must never shadow a stale disk copy)."""
        lib = self._lib
        while True:
            need = int(lib.ptps_evicted_bytes(self._h))
            if not need:
                return
            buf = np.empty(need, np.uint8)
            got = int(lib.ptps_drain_evicted(self._h, _u8_ptr(buf), need))
            if not got:
                return
            # parse the shard-concatenated records, grouped per
            # (dim, nbytes) for the batched (slab-slice) spill path;
            # the header walk stays a (cheap) loop — record lengths are
            # data-dependent — but the payload copy is ONE fancy-index
            # gather per group instead of per-record slices + np.stack
            groups = {}
            off = 0
            while off + _DRAIN_REC.size <= got:
                sign, dim, nbytes = _DRAIN_REC.unpack_from(buf, off)
                off += _DRAIN_REC.size
                g = groups.setdefault((dim, nbytes), ([], []))
                g[0].append(sign)
                g[1].append(off)
                off += nbytes
            for (dim, nbytes), (signs, offs) in groups.items():
                signs = np.array(signs, np.uint64)
                starts = np.asarray(offs, np.int64)
                mat = buf[starts[:, None]
                          + np.arange(nbytes, dtype=np.int64)[None, :]]
                resident = np.zeros(len(signs), np.uint8)
                lib.ptps_contains(self._h, _u64_ptr(signs), len(signs),
                                  _u8_ptr(resident))
                keep = resident == 0
                if keep.any():
                    self.spill.put_batch(signs[keep], dim, mat[keep])

    def _fault_in(self, signs: np.ndarray, training: bool) -> np.ndarray:
        """Promote any spilled batch signs back into the native store
        (training) or report which are spilled (read paths). Returns
        the spilled-sign mask."""
        mask = self.spill.contains_batch(signs)
        if training and mask.any():
            for s in signs[mask].tolist():
                got = self.spill.take(s)
                if got is None:
                    continue
                dim0, raw = got
                vec = self._widen_raw(dim0, raw)
                self._lib.ptps_set_entry(self._h, s, dim0, _f32_ptr(vec),
                                         len(vec))
            # deliberately NOT drained here: rows these promotions evict
            # stay in the store's drain buffer through the upcoming data
            # call, whose misses fault them back from there (the
            # intra-batch evict-then-reaccess case); the caller drains
            # after its native call
        return mask

    def _widen_raw(self, dim: int, raw: np.ndarray) -> np.ndarray:
        rp = self._rp
        vec = np.empty(dim + (len(raw) - dim * rp.itemsize) // 4,
                       np.float32)
        vec[:dim] = raw[: dim * rp.itemsize].view(rp.np_dtype) \
            .astype(np.float32)
        vec[dim:] = raw[dim * rp.itemsize:].view(np.float32)
        return vec

    # --- data plane -------------------------------------------------------

    def lookup(self, signs: np.ndarray, dim: int, training: bool) -> np.ndarray:
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._lookup_impl(signs=signs, dim=dim, training=training)

    def _lookup_impl(self, signs: np.ndarray, dim: int, training: bool) -> np.ndarray:
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        out = np.empty((len(signs), dim), dtype=np.float32)
        if len(signs) == 0:
            return out
        if self.hotness is not None:
            self.hotness.observe(dim, signs)
        spilled = None
        if self.spill is not None and len(self.spill):
            spilled = self._fault_in(signs, training)
        if not training and spilled is not None and spilled.any():
            # read-only lookups PEEK the disk tier (residency must not
            # change); the native call sees only the resident signs
            sub = np.ascontiguousarray(signs[~spilled])
            sub_out = np.empty((len(sub), dim), np.float32)
            if len(sub):
                rc = self._lib.ptps_lookup(self._h, _u64_ptr(sub), len(sub),
                                           dim, 0, _f32_ptr(sub_out))
                if rc != 0:
                    raise RuntimeError("native lookup failed")
            out[~spilled] = sub_out
            for j in np.nonzero(spilled)[0]:
                got = self.spill.peek(int(signs[j]))
                if got is not None and got[0] == dim:
                    out[j] = self._widen_raw(dim, got[1])[:dim]
                else:
                    out[j] = 0.0
            return out
        rc = self._lib.ptps_lookup(self._h, _u64_ptr(signs), len(signs), dim,
                                   1 if training else 0, _f32_ptr(out))
        if rc != 0:
            raise RuntimeError(
                "native lookup failed (optimizer not registered or store "
                "not configured)"
            )
        if training and self.spill is not None:
            self._drain_evictions()
        return out

    def update_gradients(self, signs: np.ndarray, grads: np.ndarray, dim: int):
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._update_gradients_impl(signs=signs, grads=grads, dim=dim)

    def _update_gradients_impl(self, signs: np.ndarray, grads: np.ndarray, dim: int):
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        grads = np.ascontiguousarray(grads, dtype=np.float32)
        if len(signs) == 0:
            return
        if self.spill is not None and len(self.spill):
            # a gradient for a spilled row faults it in first — a
            # demotion must not turn updates into misses
            self._fault_in(signs, True)
        rc = self._lib.ptps_update(self._h, _u64_ptr(signs), len(signs), dim,
                                   _f32_ptr(grads))
        if rc != 0:
            raise RuntimeError("native update failed (optimizer not registered)")
        if self.spill is not None:
            self._drain_evictions()

    def get_entry(self, sign: int) -> Optional[Tuple[int, np.ndarray]]:
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._get_entry_impl(sign=sign)

    def _get_entry_impl(self, sign: int) -> Optional[Tuple[int, np.ndarray]]:
        dim_out = ctypes.c_uint32(0)
        length = self._lib.ptps_get_entry(self._h, sign, None, 0,
                                          ctypes.byref(dim_out))
        if length < 0:
            if self.spill is not None:
                got = self.spill.peek(int(sign))
                if got is not None:
                    dim0, raw = got
                    return dim0, self._widen_raw(dim0, raw)
            return None
        buf = np.empty(length, dtype=np.float32)
        self._lib.ptps_get_entry(self._h, sign, _f32_ptr(buf), length,
                                 ctypes.byref(dim_out))
        return int(dim_out.value), buf

    def set_entry(self, sign: int, dim: int, vec: np.ndarray):
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._set_entry_impl(sign=sign, dim=dim, vec=vec)

    def _set_entry_impl(self, sign: int, dim: int, vec: np.ndarray):
        vec = np.ascontiguousarray(vec, dtype=np.float32)
        if self.spill is not None:
            self.spill.discard(int(sign))
        self._lib.ptps_set_entry(self._h, sign, dim, _f32_ptr(vec), len(vec))
        if self.spill is not None:
            self._drain_evictions()

    def get_entries(self, signs: np.ndarray, width: int):
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._get_entries_impl(signs=signs, width=width)

    def _get_entries_impl(self, signs: np.ndarray, width: int):
        """Batched get_entry (uniform width; absent/mismatched width =>
        not found). With the SIMD-era ABI this is ONE GIL-released
        foreign call (ptps_get_entries) that widens straight out of the
        slabs; a pre-SIMD library falls back to the per-sign loop."""
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        n = len(signs)
        found = np.zeros(n, dtype=bool)
        vecs = np.zeros((n, width), dtype=np.float32)
        if n == 0:
            return found, vecs
        if "batched_entries" in self._caps:
            lens = np.empty(n, dtype=np.int64)
            self._lib.ptps_get_entries(self._h, _u64_ptr(signs), n, width,
                                       _f32_ptr(vecs), _i64_ptr(lens))
            found = lens == width
            # a resident row of the wrong width counts as not-found and
            # must come back zero (the native call wrote its prefix)
            mismatched = (lens >= 0) & ~found
            if mismatched.any():
                vecs[mismatched] = 0.0
            if self.spill is not None and len(self.spill):
                for i in np.nonzero(lens < 0)[0]:
                    got = self.spill.peek(int(signs[i]))
                    if got is None:
                        continue
                    dim0, raw = got
                    vec = self._widen_raw(dim0, raw)
                    if len(vec) == width:
                        found[i] = True
                        vecs[i] = vec
            return found, vecs
        dim_out = ctypes.c_uint32(0)
        buf = np.empty(width, dtype=np.float32)
        for i in range(n):
            length = self._lib.ptps_get_entry(
                self._h, int(signs[i]), _f32_ptr(buf), width,
                ctypes.byref(dim_out))
            if length == width:
                found[i] = True
                vecs[i] = buf
            elif length < 0 and self.spill is not None:
                got = self.spill.peek(int(signs[i]))
                if got is None:
                    continue
                dim0, raw = got
                vec = self._widen_raw(dim0, raw)
                if len(vec) == width:
                    found[i] = True
                    vecs[i] = vec
        return found, vecs

    def set_entries(self, signs: np.ndarray, dim: int, vecs: np.ndarray):
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._set_entries_impl(signs=signs, dim=dim, vecs=vecs)

    def _set_entries_impl(self, signs: np.ndarray, dim: int, vecs: np.ndarray):
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        vecs = np.ascontiguousarray(vecs, dtype=np.float32)
        if len(signs) == 0:
            return
        if "batched_entries" in self._caps:
            # ONE GIL-released foreign call narrows the whole group
            # straight into the slabs (the reshard-install hot path)
            if self.spill is not None:
                for s in signs.tolist():
                    self.spill.discard(int(s))
            rc = self._lib.ptps_set_entries(self._h, _u64_ptr(signs),
                                            len(signs), dim, _f32_ptr(vecs),
                                            vecs.shape[1])
            if rc != 0:
                raise RuntimeError("native set_entries failed (len < dim)")
        else:
            for i in range(len(signs)):
                if self.spill is not None:
                    self.spill.discard(int(signs[i]))
                self._lib.ptps_set_entry(self._h, int(signs[i]), dim,
                                         _f32_ptr(vecs[i]), vecs.shape[1])
        if self.spill is not None:
            self._drain_evictions()

    def clear(self):
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._clear_impl()

    def _clear_impl(self):
        self._lib.ptps_clear(self._h)
        if self.spill is not None:
            self.spill.clear()

    def __len__(self) -> int:
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._len_impl()

    def _len_impl(self) -> int:
        n = int(self._lib.ptps_len(self._h))
        if self.spill is not None:
            n += len(self.spill)
        return n

    # --- observables ------------------------------------------------------

    @property
    def index_miss_count(self) -> int:
        return int(self._lib.ptps_index_miss_count(self._h))

    @property
    def gradient_id_miss_count(self) -> int:
        return int(self._lib.ptps_gradient_id_miss_count(self._h))

    @property
    def resident_bytes(self) -> int:
        if not self._caps:
            return -1  # pre-arena .so: no byte accounting
        return int(self._lib.ptps_resident_bytes(self._h))

    @property
    def resident_emb_bytes(self) -> int:
        if not self._caps:
            return -1
        return int(self._lib.ptps_resident_emb_bytes(self._h))

    def resident_bytes_per_shard(self):
        if not self._caps:
            return []
        out = np.zeros(self.num_internal_shards, np.uint64)
        self._lib.ptps_shard_resident_bytes(self._h, _u64_ptr(out))
        return [int(b) for b in out]

    def arena_stats(self):
        if not self._caps:
            return {}
        out = np.zeros(4, np.uint64)
        self._lib.ptps_arena_stats(self._h, _u64_ptr(out))
        slab, free_slots, live, logical = (int(x) for x in out)
        alloc = free_slots + live
        return {"slab_bytes": slab, "free_slots": free_slots,
                "live_rows": live, "resident_bytes": logical,
                "fragmentation_ratio": (round(free_slots / alloc, 6)
                                        if alloc else 0.0)}

    def row_nbytes(self, dim: int) -> int:
        from persia_tpu.ps.optim import SparseOptimizer

        space = 0
        if self.optimizer is not None:
            space = SparseOptimizer.from_config(
                dict(self.optimizer)).require_space(dim)
        return self._rp.entry_nbytes(dim, space)

    def spill_stats(self) -> dict:
        return self.spill.stats() if self.spill is not None else {}

    def hotness_snapshot(self) -> dict:
        from persia_tpu import hotness as _hotness

        if self.hotness is None:
            return _hotness.disabled_snapshot()
        snap = self.hotness.snapshot()
        # stamp the LIVE bytes/row so planner_report budgets against the
        # real storage width (same contract as the Python holders)
        for table, t in snap.get("tables", {}).items():
            t["row_bytes"] = int(table) * self._rp.itemsize
        return snap

    # --- serialization ----------------------------------------------------

    def dump_file(self, path: str):
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._dump_file_impl(path=path)

    def _dump_file_impl(self, path: str):
        if self.spill is None:
            if self._lib.ptps_dump(self._h, path.encode()) != 0:
                raise IOError(f"native dump to {path} failed")
            return
        # spill-armed: a checkpoint is the LOGICAL table. The store
        # dumps its resident rows; spill records append behind them and
        # dump-window capture records (rows that LEFT the disk tier
        # mid-dump) prepend with lowest load priority — the same
        # shards-then-spill-with-capture discipline as the Python
        # holders, over the same record encodings.
        rp = self._rp
        self.spill.start_dump_capture()
        tmp = path + ".native_part"
        try:
            if self._lib.ptps_dump(self._h, tmp.encode()) != 0:
                raise IOError(f"native dump to {tmp} failed")
            code = _ROW_DTYPE_CODES[self.row_dtype]

            def rec(version, sign, dim, raw):
                if version == 1:
                    return (struct.pack("<QII", sign, dim, len(raw) // 4)
                            + raw.tobytes())
                return (struct.pack("<QIBI", sign, dim, code,
                                    rp.state_len_of(raw, dim))
                        + raw.tobytes())

            import shutil

            head_len = 4 + struct.calcsize("<IQ")
            spill_tmp = path + ".spill_part"
            with open(tmp, "rb") as src, open(path, "wb") as dst:
                head = src.read(head_len)
                version, count = struct.unpack_from("<IQ", head, 4)
                dst.write(head)
                # spill records serialize FIRST (to a side temp, with
                # the capture window still armed — a row faulting in
                # mid-iteration must land in the capture); they append
                # behind the native body in the final file. Capture
                # records prepend with lowest load priority (any
                # shard/spill record of the same sign is newer and wins
                # on the sequential reload). The count patches into the
                # header afterwards, so the native body streams through
                # in bounded chunks instead of materializing a multi-GB
                # store in memory.
                with open(spill_tmp, "wb") as sp:
                    for sign, dim, raw in self.spill.items():
                        sp.write(rec(version, sign, dim, raw))
                        count += 1
                for sign, (dim, raw) in \
                        self.spill.stop_dump_capture().items():
                    dst.write(rec(version, sign, dim, raw))
                    count += 1
                shutil.copyfileobj(src, dst, 4 << 20)
                with open(spill_tmp, "rb") as sp:
                    shutil.copyfileobj(sp, dst, 4 << 20)
                dst.seek(8)
                dst.write(struct.pack("<Q", count))
        finally:
            self.spill.stop_dump_capture()
            for t in (tmp, path + ".spill_part"):
                try:
                    os.remove(t)
                except OSError:
                    pass

    def load_file(self, path: str, clear: bool = True):
        # serialized while spill-armed (see _mu); no-op guard else
        with self._guard():
            return self._load_file_impl(path=path, clear=clear)

    def _load_file_impl(self, path: str, clear: bool = True):
        if self._caps:
            if self.spill is not None:
                if clear:
                    # both tiers restart empty; rows the load itself
                    # evicts drain into the (fresh) spill below
                    self.spill.clear()
                else:
                    # merge-load: every loaded sign must discard any
                    # stale spilled copy (the Python holders get this
                    # from set_entry) — take the record-by-record path
                    from persia_tpu.ps.store import (iter_psd_records,
                                                     read_psd_header)

                    with open(path, "rb") as f:
                        version, count = read_psd_header(f, path)
                        for sign, dim, vec in iter_psd_records(
                                f.read, version, count):
                            self.set_entry(sign, dim, vec)
                    return
            # the arena-era store decodes both PSD versions in-tree
            if self._lib.ptps_load(self._h, path.encode(),
                                   1 if clear else 0) != 0:
                raise IOError(f"native load from {path} failed")
            if self.spill is not None:
                self._drain_evictions()
            return
        # pre-arena .so: C++ reads the (fp32) v1 layout only; decode v2
        # record-by-record here (widen to f32, store through set_entry)
        from persia_tpu.ps.store import iter_psd_records, read_psd_header

        with open(path, "rb") as f:
            version, count = read_psd_header(f, path)
            if version != 1:
                if clear:
                    self.clear()
                for sign, dim, vec in iter_psd_records(f.read, version,
                                                       count):
                    self.set_entry(sign, dim, vec)
                return
        if self._lib.ptps_load(self._h, path.encode(), 1 if clear else 0) != 0:
            raise IOError(f"native load from {path} failed")


def make_holder(capacity: int, num_internal_shards: int,
                prefer_native: bool = True, row_dtype: str = "fp32",
                capacity_bytes=None, hotness=None, spill_dir=None,
                spill_bytes=None, backend: Optional[str] = None):
    """The right holder for a storage policy, by capability negotiation
    (never by silent downgrade):

    - ``auto`` (default): the native C++ arena store when the loaded
      library's capabilities cover the policy; otherwise the Python
      arena holder, announced LOUDLY (an old pre-arena ``.so`` asked
      for fp16/byte-budget/spill lands here).
    - ``native``: require the native store (RuntimeError when the
      library is missing a needed capability).
    - ``arena``: force the Python arena holder.
    - ``python-legacy``: force the per-entry OrderedDict holder (the
      reference ``tests/test_arena.py`` compares the arena with).

    ``backend=None`` reads the ``PERSIA_PS_BACKEND`` knob;
    ``prefer_native=False`` maps ``auto`` to the Python arena holder.
    ``hotness`` arms the workload sketches on any backend (None = the
    PERSIA_HOTNESS knob)."""
    capacity_bytes = capacity_bytes or None  # 0 (config default) = off
    spill_dir = spill_dir or None
    row_dtype = row_dtype or "fp32"
    backend = backend or knobs.get("PERSIA_PS_BACKEND") or "auto"
    if backend not in ("auto", "native", "arena", "python-legacy"):
        raise ValueError(f"unknown PS backend {backend!r} (expected "
                         "auto|native|arena|python-legacy)")
    if backend == "auto" and not prefer_native:
        backend = "arena"

    def python_holder(cls):
        return cls(capacity, num_internal_shards, row_dtype=row_dtype,
                   capacity_bytes=capacity_bytes, hotness=hotness,
                   spill_dir=spill_dir, spill_bytes=spill_bytes or None)

    if backend == "python-legacy":
        from persia_tpu.ps.store import EmbeddingHolder

        return python_holder(EmbeddingHolder)
    from persia_tpu.ps.arena import ArenaEmbeddingHolder

    if backend == "arena":
        return python_holder(ArenaEmbeddingHolder)
    lib = load_native_lib()
    if lib is None:
        if backend == "native":
            raise RuntimeError(
                "PERSIA_PS_BACKEND=native but the native library is not "
                "available; run `make -C native`")
        _logger.warning("native store unavailable; using the Python arena "
                        "holder")
        return python_holder(ArenaEmbeddingHolder)
    missing = (required_capabilities(row_dtype, capacity_bytes, spill_dir)
               - native_capabilities(lib))
    if missing:
        msg = (f"loaded native library lacks {sorted(missing)} required by "
               f"the storage policy (row_dtype={row_dtype!r}, "
               f"capacity_bytes={capacity_bytes}, spill_dir={spill_dir!r})"
               " — rebuild `make -C native` for the arena-era store")
        if backend == "native":
            raise RuntimeError(msg)
        # negotiate down LOUDLY: the policy is honored, on the Python
        # arena holder — never silently dropped
        _logger.warning("%s; negotiating down to the Python arena holder",
                        msg)
        return python_holder(ArenaEmbeddingHolder)
    try:
        return NativeEmbeddingHolder(capacity, num_internal_shards,
                                     hotness=hotness, row_dtype=row_dtype,
                                     capacity_bytes=capacity_bytes,
                                     spill_dir=spill_dir,
                                     spill_bytes=spill_bytes or None)
    except RuntimeError:
        if backend == "native":
            raise
        _logger.warning("native store unavailable; using the Python arena "
                        "holder")
        return python_holder(ArenaEmbeddingHolder)
