"""Arena-backed embedding parameter store: one contiguous row arena.

The per-entry :class:`~persia_tpu.ps.store.EmbeddingHolder` keeps every
row as its own numpy object inside an OrderedDict — at 10^7..10^9 rows
that is 10^7..10^9 tracked Python objects (the gen2 GC walks that
forced the PS binary's gc freeze-and-retune at boot), ~100 bytes of
per-entry overhead on top of the data, and a per-sign interpreter loop
on every batched call. This module stores rows the way "Tensor Casting" (PAPERS.md)
treats embedding access — as a byte-addressed, layout-co-designed path:

- **Record classes.** Rows live in fixed-stride records grouped per
  ``(dim, optimizer state width)`` class. A record is ``[emb bytes
  (row_dtype) | pad to 4 | f32 optimizer state | pad to 8]``; the
  LOGICAL record (what PSD v2, the spill tier, and cross-backend parity
  see) is the unpadded ``[emb | state]`` — byte-identical with
  :class:`~persia_tpu.ps.optim.RowPrecision`'s layout and with
  ``native/src/store.h``'s arena, so all storage policies are
  implemented once over one byte layout.
- **Slab arena.** Each class owns ONE contiguous uint8 buffer grown in
  ``PERSIA_ARENA_SLAB_ROWS`` quanta (amortized-doubling realloc), with
  a free list recycling evicted slots. Strided numpy views expose the
  emb/state fields of ALL rows at once, so a batched lookup is one
  fancy-index gather and a batched update is one gather + one
  vectorized optimizer call + one scatter — no per-sign Python objects
  anywhere on the hot path. The buffers are plain (GC-invisible)
  ndarrays: a full GC walk costs the same whether the arena holds 10^3
  or 10^9 rows, and a shard is one memcpy-able byte range for live
  migration.
- **Flat sign index.** An open-addressing hash per shard maps sign ->
  packed ``(class, slot)``, probed for a whole batch in a handful of
  vectorized passes (the device-cache mapper's idiom); tombstoned
  deletes, rebuilt tombstone-free past 3/4 fill.
- **Exact LRU by stamp.** Every training access writes a per-shard
  monotone stamp; eviction pops the minimum-stamp row through a
  batch-frozen victim queue (cursor-skip on stale stamps,
  rebuild-on-exhaustion). Stamp order IS the OrderedDict recency order,
  so semantics — and the PSD v1 dump byte stream of an fp32 holder —
  match the per-entry holder exactly. When one batch could wrap a
  shard's whole row/byte budget (capacity smaller than a batch: the
  only case where batched insert-then-evict could diverge from the
  reference's per-sign sequence), the shard falls back to an exact
  sequential path.

Interface, semantics, serialization (PSD v1/v2), spill demotion, and
telemetry are all those of ``EmbeddingHolder`` — the two are
interchangeable, and ``ps.native.make_holder`` returns this holder for
the Python backend (``PERSIA_PS_BACKEND=python-legacy`` restores the
per-entry holder as an A/B lever).

Lock discipline: the holder owns nothing mutable; each ``_ArenaShard``
carries its own ``lock`` and every mutating shard method is suffixed
``_locked`` (caller holds ``shard.lock``) — the per-shard lock
convention persialint's lock pass checks.
"""

import struct
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from persia_tpu.ps.optim import (
    RowPrecision,
    SparseOptimizer,
    apply_weight_bound,
)
from persia_tpu.ps.rng import admit_mask, initialize_entries, internal_shard_of
from persia_tpu.ps.store import DUMP_MAGIC, _DTYPE_CODES, iter_psd_records, \
    read_psd_header

_H_MULT = 0x9E3779B97F4A7C15  # fibonacci multiplier, splits u64 keys
_SLOT_BITS = 44  # packed index value: (class << 44) | slot
_SLOT_MASK = (1 << _SLOT_BITS) - 1


def _slab_rows() -> int:
    from persia_tpu import knobs

    return max(1024, int(knobs.get("PERSIA_ARENA_SLAB_ROWS")))


class _RowClass:
    """One record class: all rows of one ``(dim, state space)`` shape in
    one contiguous strided buffer plus parallel metadata arrays. All
    mutation happens under the owning shard's lock."""

    __slots__ = ("dim", "space", "np_dtype", "itemsize", "emb_bytes",
                 "emb_pad", "stride", "logical_bytes", "cap", "data", "emb",
                 "state", "signs", "stamps", "free", "next_fresh", "live",
                 "slab_rows")

    def __init__(self, dim: int, space: int, rp: RowPrecision,
                 slab_rows: int):
        self.dim = dim
        self.space = space
        self.np_dtype = rp.np_dtype
        self.itemsize = rp.itemsize
        self.emb_bytes = dim * rp.itemsize
        self.emb_pad = (self.emb_bytes + 3) & ~3
        self.stride = (self.emb_pad + 4 * space + 7) & ~7
        self.logical_bytes = self.emb_bytes + 4 * space
        self.slab_rows = slab_rows
        self.cap = 0
        self.data: Optional[np.ndarray] = None
        self.emb: Optional[np.ndarray] = None
        self.state: Optional[np.ndarray] = None
        self.signs: Optional[np.ndarray] = None
        self.stamps: Optional[np.ndarray] = None
        self.free: List[int] = []
        self.next_fresh = 0
        self.live = 0

    def _grow(self, need_rows: int):
        new_cap = max(self.cap * 2, self.slab_rows)
        while new_cap < need_rows:
            new_cap += self.slab_rows
        data = np.zeros(new_cap * self.stride, np.uint8)
        signs = np.zeros(new_cap, np.uint64)
        stamps = np.full(new_cap, -1, np.int64)
        if self.cap:
            data[: self.cap * self.stride] = self.data
            signs[: self.cap] = self.signs
            stamps[: self.cap] = self.stamps
        self.cap = new_cap
        self.data = data
        self.signs = signs
        self.stamps = stamps
        self.emb = np.ndarray((new_cap, self.dim), dtype=self.np_dtype,
                              buffer=data, strides=(self.stride,
                                                    self.itemsize))
        self.state = (np.ndarray((new_cap, self.space), dtype=np.float32,
                                 buffer=data, offset=self.emb_pad,
                                 strides=(self.stride, 4))
                      if self.space else None)

    def alloc_locked(self, k: int) -> np.ndarray:
        """k fresh/recycled slot ids (free list LIFO first)."""
        out = np.empty(k, np.int64)
        reuse = min(k, len(self.free))
        for i in range(reuse):
            out[i] = self.free.pop()
        fresh = k - reuse
        if fresh:
            if self.next_fresh + fresh > self.cap:
                self._grow(self.next_fresh + fresh)
            out[reuse:] = np.arange(self.next_fresh,
                                    self.next_fresh + fresh)
            self.next_fresh += fresh
        self.live += k
        return out

    def free_locked(self, slot: int):
        self.stamps[slot] = -1
        self.free.append(slot)
        self.live -= 1

    def logical_rows_locked(self, slots: np.ndarray) -> np.ndarray:
        """Extract the logical ``[emb bytes | state f32 bytes]`` records
        of ``slots`` as one (k, logical_bytes) uint8 matrix (two
        vectorized field copies — the spill tier's slab-slice demotion
        path and the checkpoint's record source)."""
        k = len(slots)
        out = np.empty((k, self.logical_bytes), np.uint8)
        out[:, : self.emb_bytes] = (
            np.ascontiguousarray(self.emb[slots]).view(np.uint8))
        if self.space:
            out[:, self.emb_bytes:] = (
                np.ascontiguousarray(self.state[slots]).view(np.uint8))
        return out

    def write_raw_locked(self, slot: int, raw: np.ndarray):
        """Store a logical record byte-exactly (spill fault-in /
        cross-backend record import)."""
        self.emb[slot] = raw[: self.emb_bytes].view(self.np_dtype)
        if self.space:
            self.state[slot] = raw[self.emb_bytes:].view(np.float32)

    def slab_bytes(self) -> int:
        return self.cap * self.stride


class _ArenaShard:
    """One internal shard: its record classes, flat sign index, stamp
    clock, victim queue, and byte accounting. ``lock`` is acquired by
    the HOLDER around every ``*_locked`` call (the arena's per-shard
    lock convention)."""

    def __init__(self, capacity: int, byte_capacity: Optional[int],
                 rp: RowPrecision, slab_rows: int, index_slots: int):
        self.lock = threading.Lock()
        self.capacity = capacity
        self.byte_capacity = byte_capacity
        self.rp = rp
        self.slab_rows = slab_rows
        self.classes: List[_RowClass] = []
        self._class_of: Dict[Tuple[int, int], int] = {}
        self.resident_bytes = 0
        self.emb_bytes = 0
        self.clock = 0
        # open-addressing sign -> packed (class << 44 | slot); value -1
        # empty, -2 tombstone (sign 0 is a legal key)
        size = 8
        while size < index_slots:
            size <<= 1
        self._h_size = size
        self._h_mask = size - 1
        self._h_shift = 65 - size.bit_length()
        self._h_sign = np.zeros(size, np.uint64)
        self._h_val = np.full(size, -1, np.int64)
        self._h_fill = 0  # occupied + tombstones (bounds probe chains)
        # batch-frozen victim queue (stamp-ascending), cursor-skip on
        # stale stamps, rebuilt on exhaustion
        self._vq_cls: Optional[np.ndarray] = None
        self._vq_slot: Optional[np.ndarray] = None
        self._vq_stamp: Optional[np.ndarray] = None
        self._vq_cursor = 0

    # --- record classes -------------------------------------------------

    def class_id_locked(self, dim: int, space: int,
                        create: bool = True) -> Optional[int]:
        cid = self._class_of.get((dim, space))
        if cid is None and create:
            cid = len(self.classes)
            self.classes.append(_RowClass(dim, space, self.rp,
                                          self.slab_rows))
            self._class_of[(dim, space)] = cid
        return cid

    def live_rows(self) -> int:
        return sum(c.live for c in self.classes)

    # --- flat sign index ------------------------------------------------

    def probe_locked(self, keys: np.ndarray) -> np.ndarray:
        """Bulk lookup: packed int64 value per key, -1 for absent. Each
        round resolves every key whose probe cell is a hit or a virgin
        empty; mismatches and tombstones advance one cell."""
        mask = self._h_mask
        out = np.full(len(keys), -1, np.int64)
        idx = ((keys * np.uint64(_H_MULT))
               >> np.uint64(self._h_shift)).astype(np.int64)
        pend = np.arange(len(keys))
        kp = keys
        h_val, h_sign = self._h_val, self._h_sign
        while len(pend):
            v = h_val[idx]
            found = (v >= 0) & (h_sign[idx] == kp)
            if found.any():
                out[pend[found]] = v[found]
            cont = ~found & (v != -1)
            pend = pend[cont]
            kp = kp[cont]
            idx = (idx[cont] + 1) & mask
        return out

    def _h_find(self, sign: int) -> int:
        mask = self._h_mask
        h_val, h_sign = self._h_val, self._h_sign
        i = ((sign * _H_MULT) & 0xFFFFFFFFFFFFFFFF) >> self._h_shift
        while True:
            v = h_val[i]
            if v == -1:
                return -1
            if v >= 0 and h_sign[i] == sign:
                return i
            i = (i + 1) & mask

    def index_put_locked(self, sign: int, packed: int):
        """Insert/overwrite one index entry (scalar; callers loop —
        insert batches are the cold fill/eviction paths)."""
        i = self._h_find(sign)
        if i >= 0:
            self._h_val[i] = packed
            return
        mask = self._h_mask
        h_val = self._h_val
        i = ((sign * _H_MULT) & 0xFFFFFFFFFFFFFFFF) >> self._h_shift
        while h_val[i] >= 0:
            i = (i + 1) & mask
        if h_val[i] == -1:
            self._h_fill += 1
        self._h_sign[i] = sign
        h_val[i] = packed
        if 4 * self._h_fill > 3 * self._h_size:
            self._h_rebuild_locked()

    def index_del_locked(self, sign: int):
        i = self._h_find(sign)
        if i >= 0:
            self._h_val[i] = -2  # tombstone

    def _h_rebuild_locked(self):
        """Grow/compact the index from its own LIVE entries — never
        from stamps: the batched insert path stamps rows only after
        all its index inserts, so a mid-batch rebuild keyed on stamps
        would silently drop every row inserted earlier in that batch
        (ghost rows: allocated + accounted but unreachable)."""
        old_sign, old_val = self._h_sign, self._h_val
        sel = np.nonzero(old_val >= 0)[0]
        live = len(sel)
        size = self._h_size
        while size < 4 * max(live, 1):
            size <<= 1
        self._h_size = size
        self._h_mask = size - 1
        self._h_shift = 65 - size.bit_length()
        self._h_sign = np.zeros(size, np.uint64)
        self._h_val = np.full(size, -1, np.int64)
        h_sign, h_val = self._h_sign, self._h_val
        mask = self._h_mask
        for sign, val in zip(old_sign[sel].tolist(),
                             old_val[sel].tolist()):
            i = ((sign * _H_MULT) & 0xFFFFFFFFFFFFFFFF) \
                >> self._h_shift
            while h_val[i] >= 0:
                i = (i + 1) & mask
            h_sign[i] = sign
            h_val[i] = val
        self._h_fill = live

    # --- stamps / eviction ----------------------------------------------

    def stamp_batch_locked(self, cls_ids: np.ndarray, slots: np.ndarray,
                           has_dups: bool):
        """Refresh recency for the accessed rows, in access order (the
        OrderedDict move-to-end sequence). Duplicate positions keep the
        LAST occurrence's stamp via maximum.at (stamps grow with batch
        position)."""
        n = len(slots)
        if n == 0:
            return
        stamps = np.arange(self.clock, self.clock + n, dtype=np.int64)
        self.clock += n
        for cid in np.unique(cls_ids):
            m = cls_ids == cid
            cls = self.classes[cid]
            if has_dups:
                np.maximum.at(cls.stamps, slots[m], stamps[m])
            else:
                cls.stamps[slots[m]] = stamps[m]

    def stamp_one_locked(self, cls_id: int, slot: int):
        self.classes[cls_id].stamps[slot] = self.clock
        self.clock += 1

    def _vq_rebuild_locked(self):
        parts = []
        for cid, cls in enumerate(self.classes):
            rows = np.nonzero(cls.stamps[: cls.next_fresh] >= 0)[0]
            if len(rows):
                parts.append((np.full(len(rows), cid, np.int64), rows,
                              cls.stamps[rows]))
        if not parts:
            self._vq_cls = self._vq_slot = self._vq_stamp = \
                np.empty(0, np.int64)
            self._vq_cursor = 0
            return
        cls_ids = np.concatenate([p[0] for p in parts])
        slots = np.concatenate([p[1] for p in parts])
        stamps = np.concatenate([p[2] for p in parts])
        order = np.argsort(stamps, kind="stable")
        self._vq_cls = cls_ids[order]
        self._vq_slot = slots[order]
        self._vq_stamp = stamps[order]
        self._vq_cursor = 0

    def pop_victim_locked(self) -> Optional[Tuple[int, int]]:
        """(class, slot) of the least-recently-stamped live row; None
        when the shard is empty. Stale queue entries (row refreshed or
        freed since the freeze) are skipped by stamp comparison."""
        for _ in range(2):  # current queue, then one rebuild
            if self._vq_stamp is not None:
                vq_stamp, vq_cls, vq_slot = (self._vq_stamp, self._vq_cls,
                                             self._vq_slot)
                i = self._vq_cursor
                n = len(vq_stamp)
                while i < n:
                    cid = vq_cls[i]
                    slot = vq_slot[i]
                    if self.classes[cid].stamps[slot] == vq_stamp[i]:
                        self._vq_cursor = i + 1
                        return int(cid), int(slot)
                    i += 1
                self._vq_cursor = n
            if self.live_rows() == 0:
                return None
            self._vq_rebuild_locked()
        return None

    def over_budget_locked(self, floor_rows: int = 0) -> bool:
        live = self.live_rows()
        return live > self.capacity or (
            self.byte_capacity is not None
            and self.resident_bytes > self.byte_capacity
            and live > max(1, floor_rows))

    def evict_locked(self, spill_rows: Optional[List]) -> int:
        """Restore the row/byte budget; returns rows evicted. With
        ``spill_rows`` a list, evicted rows are appended as
        ``(sign, dim, cls_id, slot)`` for the caller's grouped spill
        demotion (``extract_spill_locked``) — a freed slot keeps its
        bytes until reallocated, so extraction right after is exact."""
        evicted = 0
        while self.over_budget_locked():
            victim = self.pop_victim_locked()
            if victim is None:
                break
            cid, slot = victim
            cls = self.classes[cid]
            sign = int(cls.signs[slot])
            self.index_del_locked(sign)
            self.resident_bytes -= cls.logical_bytes
            self.emb_bytes -= cls.emb_bytes
            if spill_rows is not None:
                spill_rows.append((sign, cls.dim, cid, slot))
            cls.free_locked(slot)
            evicted += 1
        return evicted

    def free_entry_locked(self, cid: int, slot: int):
        """Release one live row (dim-mismatch reinit path)."""
        cls = self.classes[cid]
        self.resident_bytes -= cls.logical_bytes
        self.emb_bytes -= cls.emb_bytes
        cls.free_locked(slot)

    def extract_spill_locked(self, spill_rows: List):
        """Group the rows ``evict_locked`` collected per class and
        extract their logical bytes in one vectorized pass per class:
        [(signs u64 array, dim, (k, logical) uint8 matrix), ...].
        Valid only immediately after eviction — freed slots keep their
        bytes until reallocated."""
        out = []
        by_class: Dict[int, List[Tuple[int, int]]] = {}
        for sign, dim, cid, slot in spill_rows:
            by_class.setdefault(cid, []).append((sign, slot))
        for cid, pairs in by_class.items():
            cls = self.classes[cid]
            signs = np.array([p[0] for p in pairs], np.uint64)
            slots = np.array([p[1] for p in pairs], np.int64)
            out.append((signs, cls.dim, cls.logical_rows_locked(slots)))
        return out

    # --- scalar row ops (fallback / debug paths) ------------------------

    def get_locked(self, sign: int) -> Optional[Tuple[int, int]]:
        packed = self._h_find(sign)
        if packed < 0:
            return None
        v = int(self._h_val[packed])
        return v >> _SLOT_BITS, v & _SLOT_MASK

    def insert_row_locked(self, sign: int, dim: int, full_f32: np.ndarray,
                          raw: Optional[np.ndarray] = None) -> Tuple[int,
                                                                     int]:
        """Insert/replace one row (refreshing recency), WITHOUT budget
        enforcement — the caller runs eviction after. ``raw`` given
        stores logical bytes exactly; else ``full_f32`` narrows in."""
        space = (len(raw) - dim * self.rp.itemsize) // 4 if raw is not None \
            else len(full_f32) - dim
        cid = self.class_id_locked(dim, space)
        cls = self.classes[cid]
        existing = self.get_locked(sign)
        if existing is not None and existing[0] == cid:
            slot = existing[1]
        else:
            if existing is not None:
                ocls = self.classes[existing[0]]
                self.resident_bytes -= ocls.logical_bytes
                self.emb_bytes -= ocls.emb_bytes
                ocls.free_locked(existing[1])
            slot = int(cls.alloc_locked(1)[0])
            cls.signs[slot] = sign
            self.index_put_locked(sign, (cid << _SLOT_BITS) | slot)
            self.resident_bytes += cls.logical_bytes
            self.emb_bytes += cls.emb_bytes
        if raw is not None:
            cls.write_raw_locked(slot, raw)
        else:
            cls.emb[slot] = full_f32[:dim]
            if cls.space:
                cls.state[slot] = full_f32[dim:]
        self.stamp_one_locked(cid, slot)
        return cid, slot

    def stats_locked(self) -> Dict[str, int]:
        allocated = sum(c.next_fresh for c in self.classes)
        return {
            "slab_bytes": sum(c.slab_bytes() for c in self.classes),
            "free_slots": sum(len(c.free) for c in self.classes),
            "live_rows": self.live_rows(),
            "allocated_rows": allocated,
            "resident_bytes": self.resident_bytes,
        }


class ArenaEmbeddingHolder:
    """Drop-in twin of :class:`~persia_tpu.ps.store.EmbeddingHolder`
    over the contiguous row arena (module docstring has the layout).
    Same constructor policy surface: ``row_dtype`` narrows the stored
    embedding slice, ``capacity_bytes`` arms byte-accounted eviction,
    ``spill_dir`` demotes evictions to the disk tier, ``hotness`` arms
    the workload sketches."""

    releases_gil = False

    def __init__(self, capacity: int = 1_000_000_000,
                 num_internal_shards: int = 8, row_dtype: str = "fp32",
                 capacity_bytes: Optional[int] = None,
                 hotness: Optional[bool] = None,
                 spill_dir: Optional[str] = None,
                 spill_bytes: Optional[int] = None):
        if num_internal_shards <= 0:
            raise ValueError("num_internal_shards must be positive")
        from persia_tpu import knobs

        capacity_bytes = capacity_bytes or None
        self.capacity = capacity
        self.capacity_bytes = capacity_bytes
        self.num_internal_shards = num_internal_shards
        self._rp = RowPrecision(row_dtype)
        per_shard = max(1, capacity // num_internal_shards)
        per_shard_bytes = (
            max(1, capacity_bytes // num_internal_shards)
            if capacity_bytes is not None else None)
        slab_rows = _slab_rows()
        index_slots = max(8, int(knobs.get("PERSIA_ARENA_INDEX_SLOTS")))
        self._shards = [
            _ArenaShard(per_shard, per_shard_bytes, self._rp, slab_rows,
                        index_slots)
            for _ in range(num_internal_shards)
        ]
        self.optimizer: Optional[SparseOptimizer] = None
        self.init_method: str = "bounded_uniform"
        self.init_params: dict = {"lower": -0.01, "upper": 0.01}
        self.admit_probability: float = 1.0
        self.weight_bound: float = 10.0
        self.enable_weight_bound: bool = True
        self.configured = False
        self._index_miss = [0] * num_internal_shards
        self._gradient_id_miss = [0] * num_internal_shards
        self._miss_counters: Dict[Tuple[str, int], object] = {}
        from persia_tpu import hotness as _hotness

        self.hotness = _hotness.make_tracker(num_internal_shards,
                                             enabled=hotness)
        if spill_dir:
            from persia_tpu.ps.spill import SpillStore

            self.spill: Optional["SpillStore"] = SpillStore(
                spill_dir, max_bytes=spill_bytes or None)
        else:
            self.spill = None

    # --- mirrored observables -------------------------------------------

    @property
    def row_dtype(self) -> str:
        return self._rp.name

    @property
    def resident_bytes(self) -> int:
        return sum(s.resident_bytes for s in self._shards)

    @property
    def resident_emb_bytes(self) -> int:
        return sum(s.emb_bytes for s in self._shards)

    def resident_bytes_per_shard(self) -> List[int]:
        return [s.resident_bytes for s in self._shards]

    def row_nbytes(self, dim: int) -> int:
        space = self.optimizer.require_space(dim) if self.optimizer else 0
        return self._rp.entry_nbytes(dim, space)

    @property
    def index_miss_count(self) -> int:
        return sum(self._index_miss)

    @property
    def gradient_id_miss_count(self) -> int:
        return sum(self._gradient_id_miss)

    def arena_stats(self) -> Dict[str, int]:
        """Aggregated slab accounting for the ``ps_arena_*`` gauges:
        allocated slab bytes, reusable free slots, live rows, logical
        resident bytes, and the fragmentation ratio (1 - live/allocated
        rows — eviction-churned slots not yet refilled)."""
        totals = {"slab_bytes": 0, "free_slots": 0, "live_rows": 0,
                  "allocated_rows": 0, "resident_bytes": 0}
        for shard in self._shards:
            with shard.lock:
                for k, v in shard.stats_locked().items():
                    totals[k] += v
        alloc = totals.pop("allocated_rows")
        totals["fragmentation_ratio"] = (
            round(1.0 - totals["live_rows"] / alloc, 6) if alloc else 0.0)
        return totals

    def _bump_miss(self, kind: str, dim: int, n: int):
        # racing first-use builds the cell twice; the registry dedups by
        # (name, labels), so both writers land on the same Counter
        key = (kind, dim)
        c = self._miss_counters.get(key)
        if c is None:
            from persia_tpu.metrics import default_registry

            c = self._miss_counters[key] = default_registry().counter(
                f"ps_{kind}_total", {"table": str(dim)},
                help_text=(
                    "eval/unadmitted/cold lookups that read zeros, per "
                    "embedding table (dim)" if kind == "index_miss" else
                    "gradient updates whose sign was absent or "
                    "re-laid-out, per embedding table (dim)"))
        c.inc(n)

    def hotness_snapshot(self) -> dict:
        from persia_tpu import hotness as _hotness

        if self.hotness is None:
            return _hotness.disabled_snapshot()
        snap = self.hotness.snapshot()
        for table, t in snap.get("tables", {}).items():
            t["row_bytes"] = int(table) * self._rp.itemsize
        return snap

    def spill_stats(self) -> dict:
        return self.spill.stats() if self.spill is not None else {}

    # --- control plane ---------------------------------------------------

    def configure(self, init_method: str, init_params: dict,
                  admit_probability: float = 1.0, weight_bound: float = 10.0,
                  enable_weight_bound: bool = True):
        self.init_method = init_method
        self.init_params = dict(init_params)
        self.admit_probability = admit_probability
        self.weight_bound = weight_bound
        self.enable_weight_bound = enable_weight_bound
        self.configured = True

    def register_optimizer(self, config: dict,
                           feature_index_prefix_bit: int = 0):
        self.optimizer = SparseOptimizer.from_config(
            config, feature_index_prefix_bit=feature_index_prefix_bit)

    # --- spill helpers ---------------------------------------------------

    def _demote_locked(self, shard: _ArenaShard, spill_rows: List):
        """Push the rows eviction collected down to the disk tier
        (slab-slice extraction, one vectorized pass per class)."""
        if not spill_rows:
            return
        for signs, dim, rows in shard.extract_spill_locked(spill_rows):
            self.spill.put_batch(signs, dim, rows)

    def _evict_and_spill_locked(self, shard: _ArenaShard):
        if self.spill is None:
            shard.evict_locked(None)
            return
        spill_rows: List = []
        shard.evict_locked(spill_rows)
        self._demote_locked(shard, spill_rows)

    def _fault_in_locked(self, shard: _ArenaShard, sign: int,
                         training: bool):
        """Transparent fault-in of a spilled row (same contract as the
        per-entry holder: training TAKES and re-inserts resident,
        read-only PEEKS). Returns ``(dim, raw logical bytes)`` or
        None."""
        got = (self.spill.take(sign) if training
               else self.spill.peek(sign))
        if got is None:
            return None
        dim0, raw = got
        if training:
            shard.insert_row_locked(sign, dim0, None, raw=raw)
            self._evict_and_spill_locked(shard)
        return dim0, raw

    # --- data plane -------------------------------------------------------

    def lookup(self, signs: np.ndarray, dim: int,
               training: bool) -> np.ndarray:
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        n = len(signs)
        out = np.zeros((n, dim), dtype=np.float32)
        if n == 0:
            return out
        if training:
            if self.optimizer is None:
                raise RuntimeError(
                    "optimizer not registered on parameter server")
            if not self.configured:
                raise RuntimeError("parameter server not configured")
        shard_ids = internal_shard_of(signs, self.num_internal_shards)
        if self.hotness is not None:
            # outside the shard locks: the tracker owns its own leaf
            # locks, so lookup hold times and lock order are untouched
            self.hotness.observe(dim, signs)
        if training:
            space = self.optimizer.require_space(dim)
            admitted = admit_mask(signs, self.admit_probability)
            init_vecs = np.zeros((n, dim + space), dtype=np.float32)
            init_vecs[:, :dim] = initialize_entries(
                signs, dim, self.init_method, self.init_params)
            if space:
                self.optimizer.state_initialization(init_vecs, dim)
        else:
            space = 0
            admitted = init_vecs = None
        for shard_idx in np.unique(shard_ids):
            sel = np.nonzero(shard_ids == shard_idx)[0]
            shard = self._shards[shard_idx]
            with shard.lock:
                if training:
                    n_miss = self._lookup_train_locked(
                        shard, signs[sel], sel, dim, space, init_vecs,
                        admitted, out)
                else:
                    n_miss = self._lookup_eval_locked(
                        shard, signs[sel], sel, dim, out)
            if n_miss:
                self._index_miss[shard_idx] += n_miss
                self._bump_miss("index_miss", dim, n_miss)
        return out

    def _lookup_train_locked(self, shard, ssigns, sel, dim, space,
                             init_vecs, admitted, out) -> int:
        cid = shard.class_id_locked(dim, space)
        cls = shard.classes[cid]
        # duplicate signs must see each other's inserts: exact
        # sequential path
        if len(np.unique(ssigns)) != len(ssigns):
            return self._lookup_train_seq_locked(
                shard, ssigns, sel, dim, space, init_vecs, admitted, out)
        packed = shard.probe_locked(ssigns)
        p_cls = packed >> _SLOT_BITS
        p_slot = packed & _SLOT_MASK
        # a hit is any resident class of the SAME dim (state width may
        # differ under an older optimizer layout — still a read hit,
        # like the per-entry holder's `entry[0] == dim` check)
        hit = np.zeros(len(ssigns), bool)
        for ocid in np.unique(p_cls[packed >= 0]):
            ocls = shard.classes[ocid]
            if ocls.dim != dim:
                continue
            m = (packed >= 0) & (p_cls == ocid)
            out[sel[m]] = ocls.emb[p_slot[m]]
            hit |= m
        # Batched insert-then-evict is only sequence-exact while the
        # batch evicts NOTHING: a mid-batch eviction in the reference's
        # per-sign order can claim a row this batch reads later (turning
        # its hit into a reinit). Pessimistic pre-check — any possible
        # insert pushing past the row/byte budget — reruns the shard's
        # batch on the exact sequential path instead (nothing has been
        # stamped or inserted yet; hit rows were only read). Hit-only
        # steady batches and the pre-capacity fill never take this.
        n_nonhit = int((~hit).sum())
        # byte pessimism covers spill fault-ins too: a faulted row may
        # belong to a WIDER class than this lookup's inserts
        worst_row = cls.logical_bytes
        if self.spill is not None and shard.byte_capacity is not None:
            worst_row = max(worst_row,
                            max((c.logical_bytes
                                 for c in shard.classes), default=0))
        if n_nonhit and (
                shard.live_rows() + n_nonhit > shard.capacity
                or (shard.byte_capacity is not None
                    and shard.resident_bytes + n_nonhit * worst_row
                    > shard.byte_capacity)):
            return self._lookup_train_seq_locked(
                shard, ssigns, sel, dim, space, init_vecs, admitted, out)
        # resident under another dim: reference semantics reinitialize
        # unconditionally (admission does not apply to dim mismatches)
        stale = (packed >= 0) & ~hit
        if self.spill is not None and (~hit & ~stale).any():
            # fault spilled rows back in BEFORE deciding miss-init; a
            # faulted row of the right dim becomes a plain (read) hit
            for j in np.nonzero(~hit & ~stale)[0]:
                got = self._fault_in_locked(shard, int(ssigns[j]), True)
                if got is None:
                    continue
                dim0, _raw = got
                loc = shard.get_locked(int(ssigns[j]))
                if loc is None:
                    continue
                if dim0 == dim:
                    hit[j] = True
                    p_cls[j], p_slot[j] = loc
                    packed[j] = (loc[0] << _SLOT_BITS) | loc[1]
                    out[sel[j]] = shard.classes[loc[0]].emb[loc[1]]
                else:  # spilled under another dim: reinitialize
                    stale[j] = True
                    p_cls[j], p_slot[j] = loc
                    packed[j] = (loc[0] << _SLOT_BITS) | loc[1]
        miss = ~hit & (admitted[sel] | stale)
        zeros = ~hit & ~miss
        n_miss = 0
        miss_idx = np.nonzero(miss)[0]
        if len(miss_idx):
            n_miss += len(miss_idx)
            if self.spill is not None:
                # the about-to-be-resident signs must not shadow stale
                # disk copies (ladder invariant)
                for s in ssigns[miss_idx].tolist():
                    self.spill.discard(s)
            # dim-mismatched residents release their old slots first
            for j in np.nonzero(stale)[0].tolist():
                shard.free_entry_locked(int(p_cls[j]), int(p_slot[j]))
            rows = cls.alloc_locked(len(miss_idx))
            cls.emb[rows] = init_vecs[sel[miss_idx], :dim]
            if space:
                cls.state[rows] = init_vecs[sel[miss_idx], dim:]
            cls.signs[rows] = ssigns[miss_idx]
            base = cid << _SLOT_BITS
            for s, r in zip(ssigns[miss_idx].tolist(), rows.tolist()):
                shard.index_put_locked(s, base | r)
            shard.resident_bytes += len(miss_idx) * cls.logical_bytes
            shard.emb_bytes += len(miss_idx) * cls.emb_bytes
            # caller reads the STORED value (narrow-then-widen), so a
            # lookup right after the miss reads what later lookups will
            out[sel[miss_idx]] = cls.emb[rows]
            p_cls[miss_idx] = cid
            p_slot[miss_idx] = rows
        n_miss += int(zeros.sum())
        touched = hit | miss
        shard.stamp_batch_locked(p_cls[touched], p_slot[touched],
                                 has_dups=False)
        self._evict_and_spill_locked(shard)
        return n_miss

    def _lookup_train_seq_locked(self, shard, ssigns, sel, dim, space,
                                 init_vecs, admitted, out) -> int:
        """Exact per-sign sequence (duplicates and batch-wraps-capacity
        cases): each access sees every earlier access's insertions and
        evictions, like the per-entry and native stores."""
        cid = shard.class_id_locked(dim, space)
        cls = shard.classes[cid]
        n_miss = 0
        for j, pos in enumerate(sel.tolist()):
            sign = int(ssigns[j])
            loc = shard.get_locked(sign)
            if loc is None and self.spill is not None:
                if self._fault_in_locked(shard, sign, True) is not None:
                    loc = shard.get_locked(sign)
            if loc is not None and shard.classes[loc[0]].dim == dim:
                out[pos] = shard.classes[loc[0]].emb[loc[1]]
                shard.stamp_one_locked(loc[0], loc[1])
            elif loc is None and not admitted[pos]:
                n_miss += 1
            else:
                if self.spill is not None:
                    self.spill.discard(sign)
                shard.insert_row_locked(sign, dim, init_vecs[pos])
                loc = shard.get_locked(sign)
                out[pos] = cls.emb[loc[1]]
                self._evict_and_spill_locked(shard)
                n_miss += 1
        return n_miss

    def _lookup_eval_locked(self, shard, ssigns, sel, dim, out) -> int:
        packed = shard.probe_locked(ssigns)
        p_cls = packed >> _SLOT_BITS
        p_slot = packed & _SLOT_MASK
        n_miss = 0
        hits_by_cls: Dict[int, np.ndarray] = {}
        for cid in np.unique(p_cls[packed >= 0]):
            cls = shard.classes[cid]
            if cls.dim != dim:
                continue
            m = (packed >= 0) & (p_cls == cid)
            out[sel[m]] = cls.emb[p_slot[m]]
            hits_by_cls[int(cid)] = m
        hit_any = np.zeros(len(ssigns), bool)
        for m in hits_by_cls.values():
            hit_any |= m
        missing = ~hit_any
        if self.spill is not None and missing.any():
            for j in np.nonzero(missing)[0]:
                got = self._fault_in_locked(shard, int(ssigns[j]), False)
                if got is not None and got[0] == dim:
                    raw = got[1]
                    emb = raw[: dim * self._rp.itemsize] \
                        .view(self._rp.np_dtype)
                    out[sel[j]] = emb.astype(np.float32, copy=False)
                    missing[j] = False
        n_miss += int(missing.sum())
        return n_miss

    def update_gradients(self, signs: np.ndarray, grads: np.ndarray,
                         dim: int):
        if self.optimizer is None:
            raise RuntimeError("optimizer not registered on parameter server")
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        n = len(signs)
        if n == 0:
            return
        batch_state = self.optimizer.batch_level_state(signs)
        shard_ids = internal_shard_of(signs, self.num_internal_shards)
        space = self.optimizer.require_space(dim)
        width = dim + space
        has_dups = len(np.unique(signs)) != len(signs)
        for shard_idx in np.unique(shard_ids):
            sel = np.nonzero(shard_ids == shard_idx)[0]
            shard = self._shards[shard_idx]
            with shard.lock:
                n_miss = self._update_locked(
                    shard, signs[sel], sel, grads, dim, space, width,
                    batch_state, has_dups)
            if n_miss:
                self._gradient_id_miss[shard_idx] += n_miss
                self._bump_miss("gradient_id_miss", dim, n_miss)

    def _update_locked(self, shard, ssigns, sel, grads, dim, space, width,
                       batch_state, has_dups) -> int:
        packed = shard.probe_locked(ssigns)
        n_miss = 0
        if self.spill is not None:
            # gradient for a spilled row: fault it in and apply — a
            # demotion must not turn updates into misses. Each fault-in
            # may EVICT other rows (whose freed slots can be
            # reallocated), so the whole batch re-probes afterwards —
            # a slot gathered through the pre-fault probe could belong
            # to a different row by now. Two rounds: a fault-in's own
            # eviction can demote a sign later in this batch (the
            # sequential reference faults it back at its position).
            for _ in range(2):
                missing = np.nonzero(packed < 0)[0]
                faulted = False
                for j in missing:
                    if self._fault_in_locked(shard, int(ssigns[j]),
                                             True) is not None:
                        faulted = True
                if not faulted:
                    break
                packed = shard.probe_locked(ssigns)
        cid = shard.class_id_locked(dim, space, create=False)
        if cid is None:
            return len(ssigns)
        found = (packed >= 0) & ((packed >> _SLOT_BITS) == cid)
        n_miss += int((~found).sum())
        if not found.any():
            return n_miss
        cls = shard.classes[cid]
        rows = (packed & _SLOT_MASK)[found]
        pos = sel[found]
        if has_dups:
            # duplicates apply sequentially (each step sees the
            # previous one's result, like the reference)
            mat = np.empty((1, width), np.float32)
            for r, p in zip(rows.tolist(), pos.tolist()):
                mat[0, :dim] = cls.emb[r]
                if space:
                    mat[0, dim:] = cls.state[r]
                st = (batch_state[p: p + 1]
                      if batch_state is not None else None)
                self.optimizer.update(mat, grads[p: p + 1], dim, st)
                if self.enable_weight_bound:
                    apply_weight_bound(mat[:, :dim], self.weight_bound)
                cls.emb[r] = mat[0, :dim]
                if space:
                    cls.state[r] = mat[0, dim:]
            return n_miss
        # fast path: one gather, one batched optimizer call, one
        # scatter — all strided-vectorized over the slab
        mat = np.empty((len(rows), width), np.float32)
        mat[:, :dim] = cls.emb[rows]
        if space:
            mat[:, dim:] = cls.state[rows]
        sub_state = (batch_state[pos]
                     if batch_state is not None else None)
        self.optimizer.update(mat, grads[pos], dim, sub_state)
        if self.enable_weight_bound:
            apply_weight_bound(mat[:, :dim], self.weight_bound)
        cls.emb[rows] = mat[:, :dim]
        if space:
            cls.state[rows] = mat[:, dim:]
        return n_miss

    # --- debug / checkpoint ----------------------------------------------

    def get_entry(self, sign: int) -> Optional[Tuple[int, np.ndarray]]:
        """(dim, f32 [emb|state]) or None — widened fresh copy (half)
        or a live f32 view over the arena record (fp32, the legacy
        mutate-in-place contract; like the native store's Entry
        pointer, the view is valid until the next insert — arena
        growth reallocates the slab). Spilled rows read through
        (peek)."""
        shard_idx = int(internal_shard_of(
            np.array([sign], dtype=np.uint64), self.num_internal_shards)[0])
        shard = self._shards[shard_idx]
        with shard.lock:
            loc = shard.get_locked(int(sign))
            if loc is None and self.spill is not None:
                got = self._fault_in_locked(shard, int(sign), False)
                if got is not None:
                    dim0, raw = got
                    rp = self._rp
                    vec = np.empty(dim0 + (len(raw) - dim0 * rp.itemsize)
                                   // 4, np.float32)
                    vec[:dim0] = raw[: dim0 * rp.itemsize] \
                        .view(rp.np_dtype).astype(np.float32)
                    vec[dim0:] = raw[dim0 * rp.itemsize:].view(np.float32)
                    return dim0, vec
                return None
            if loc is None:
                return None
            cid, slot = loc
            cls = shard.classes[cid]
            if self._rp.is_fp32:
                # fp32 records are contiguous f32 [emb | state]: hand
                # out the live arena row, like the per-entry holder
                vec = np.ndarray((cls.dim + cls.space,), np.float32,
                                 buffer=cls.data,
                                 offset=slot * cls.stride)
                return cls.dim, vec
            vec = np.empty(cls.dim + cls.space, np.float32)
            vec[: cls.dim] = cls.emb[slot]
            if cls.space:
                vec[cls.dim:] = cls.state[slot]
            return cls.dim, vec

    def set_entry(self, sign: int, dim: int, vec: np.ndarray):
        vec = np.ascontiguousarray(vec, dtype=np.float32)
        shard_idx = int(internal_shard_of(
            np.array([sign], dtype=np.uint64), self.num_internal_shards)[0])
        shard = self._shards[shard_idx]
        with shard.lock:
            if self.spill is not None:
                self.spill.discard(int(sign))
            shard.insert_row_locked(int(sign), dim, vec)
            self._evict_and_spill_locked(shard)

    def get_entries(self, signs: np.ndarray, width: int):
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        n = len(signs)
        found = np.zeros(n, dtype=bool)
        vecs = np.zeros((n, width), dtype=np.float32)
        shard_ids = internal_shard_of(signs, self.num_internal_shards)
        for shard_idx in np.unique(shard_ids):
            sel = np.nonzero(shard_ids == shard_idx)[0]
            shard = self._shards[shard_idx]
            with shard.lock:
                packed = shard.probe_locked(signs[sel])
                p_cls = packed >> _SLOT_BITS
                p_slot = packed & _SLOT_MASK
                for cid in np.unique(p_cls[packed >= 0]):
                    cls = shard.classes[cid]
                    if cls.dim + cls.space != width:
                        continue  # absent or different layout: not found
                    m = (packed >= 0) & (p_cls == cid)
                    rows = p_slot[m]
                    vecs[sel[m], : cls.dim] = cls.emb[rows]
                    if cls.space:
                        vecs[sel[m], cls.dim:] = cls.state[rows]
                    found[sel[m]] = True
                if self.spill is not None:
                    for j in np.nonzero(packed < 0)[0]:
                        got = self._fault_in_locked(shard,
                                                    int(signs[sel[j]]),
                                                    False)
                        if got is None:
                            continue
                        dim0, raw = got
                        state_len = (len(raw) - dim0 * self._rp.itemsize) \
                            // 4
                        if dim0 + state_len != width:
                            continue
                        vecs[sel[j], :dim0] = raw[: dim0 * self._rp
                                                  .itemsize] \
                            .view(self._rp.np_dtype).astype(np.float32)
                        if state_len:
                            vecs[sel[j], dim0:] = \
                                raw[dim0 * self._rp.itemsize:] \
                                .view(np.float32)
                        found[sel[j]] = True
        return found, vecs

    def set_entries(self, signs: np.ndarray, dim: int, vecs: np.ndarray):
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        vecs = np.ascontiguousarray(vecs, dtype=np.float32)
        shard_ids = internal_shard_of(signs, self.num_internal_shards)
        for shard_idx in np.unique(shard_ids):
            sel = np.nonzero(shard_ids == shard_idx)[0]
            shard = self._shards[shard_idx]
            with shard.lock:
                for pos in sel.tolist():
                    if self.spill is not None:
                        self.spill.discard(int(signs[pos]))
                    shard.insert_row_locked(int(signs[pos]), dim,
                                            vecs[pos])
                    self._evict_and_spill_locked(shard)

    def clear(self):
        for shard in self._shards:
            with shard.lock:
                shard.classes = []
                shard._class_of = {}
                shard.resident_bytes = 0
                shard.emb_bytes = 0
                shard.clock = 0
                shard._h_sign = np.zeros(shard._h_size, np.uint64)
                shard._h_val = np.full(shard._h_size, -1, np.int64)
                shard._h_fill = 0
                shard._vq_cls = shard._vq_slot = shard._vq_stamp = None
                shard._vq_cursor = 0
        if self.spill is not None:
            self.spill.clear()

    def __len__(self) -> int:
        n = sum(s.live_rows() for s in self._shards)
        if self.spill is not None:
            n += len(self.spill)
        return n

    # --- serialization (PSD1/PSD2, shared with store.py + store.h) -------

    def _iter_records_locked(self, shard: _ArenaShard):
        """Yield ``(sign, dim, state_len, logical bytes)`` in stamp
        (LRU) order — the OrderedDict dump order, so fp32 dumps stay
        byte-identical with the per-entry holder's."""
        parts = []
        for cid, cls in enumerate(shard.classes):
            rows = np.nonzero(cls.stamps[: cls.next_fresh] >= 0)[0]
            if len(rows):
                parts.append((cid, rows, cls.stamps[rows]))
        if not parts:
            return
        cls_ids = np.concatenate(
            [np.full(len(p[1]), p[0], np.int64) for p in parts])
        slots = np.concatenate([p[1] for p in parts])
        stamps = np.concatenate([p[2] for p in parts])
        order = np.argsort(stamps, kind="stable")
        cls_ids, slots = cls_ids[order], slots[order]
        # extract per class in slab order, then emit in stamp order
        mats: Dict[int, np.ndarray] = {}
        row_pos: Dict[int, Dict[int, int]] = {}
        for cid in np.unique(cls_ids):
            m = cls_ids == cid
            rows = slots[m]
            mats[cid] = shard.classes[cid].logical_rows_locked(rows)
            row_pos[cid] = {int(r): i for i, r in enumerate(rows)}
        for cid, slot in zip(cls_ids.tolist(), slots.tolist()):
            cls = shard.classes[cid]
            yield (int(cls.signs[slot]), cls.dim, cls.space,
                   mats[cid][row_pos[cid][slot]])

    def dump_bytes(self) -> bytes:
        rp = self._rp
        chunks = []
        count = 0
        if self.spill is not None:
            self.spill.start_dump_capture()
        try:
            if rp.is_fp32:
                for shard in self._shards:
                    with shard.lock:
                        for sign, dim, state_len, raw in \
                                self._iter_records_locked(shard):
                            chunks.append(struct.pack(
                                "<QII", sign, dim, dim + state_len))
                            chunks.append(raw.tobytes())
                            count += 1
                front = []
                if self.spill is not None:
                    for sign, dim, raw in self.spill.items():
                        chunks.append(struct.pack("<QII", sign, dim,
                                                  len(raw) // 4))
                        chunks.append(raw.tobytes())
                        count += 1
                    for sign, (dim, raw) in \
                            self.spill.stop_dump_capture().items():
                        front.append(struct.pack("<QII", sign, dim,
                                                 len(raw) // 4))
                        front.append(raw.tobytes())
                        count += 1
                return b"".join(
                    [DUMP_MAGIC, struct.pack("<IQ", 1, count)]
                    + front + chunks)
            code = _DTYPE_CODES[rp.name]
            for shard in self._shards:
                with shard.lock:
                    for sign, dim, state_len, raw in \
                            self._iter_records_locked(shard):
                        chunks.append(struct.pack("<QIBI", sign, dim, code,
                                                  state_len))
                        chunks.append(raw.tobytes())
                        count += 1
            front = []
            if self.spill is not None:
                for sign, dim, raw in self.spill.items():
                    chunks.append(struct.pack(
                        "<QIBI", sign, dim, code,
                        rp.state_len_of(raw, dim)))
                    chunks.append(raw.tobytes())
                    count += 1
                for sign, (dim, raw) in \
                        self.spill.stop_dump_capture().items():
                    front.append(struct.pack(
                        "<QIBI", sign, dim, code,
                        rp.state_len_of(raw, dim)))
                    front.append(raw.tobytes())
                    count += 1
            return b"".join(
                [DUMP_MAGIC, struct.pack("<IQ", 2, count)] + front + chunks)
        finally:
            if self.spill is not None:
                self.spill.stop_dump_capture()

    def load_bytes(self, buf: bytes, clear: bool = True):
        import io

        reader = io.BytesIO(buf)
        version, count = read_psd_header(reader, "<load_bytes>")
        if clear:
            self.clear()
        for sign, dim, vec in iter_psd_records(reader.read, version, count):
            self.set_entry(sign, dim, vec)

    def dump_file(self, path: str):
        with open(path, "wb") as f:
            f.write(self.dump_bytes())

    def load_file(self, path: str, clear: bool = True):
        with open(path, "rb") as f:
            self.load_bytes(f.read(), clear=clear)
