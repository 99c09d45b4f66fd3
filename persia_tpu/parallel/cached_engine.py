"""Orchestration for the device-resident embedding cache.

Ties together the host-side LRU sign->slot map + victim buffer
(persia_tpu/worker/device_cache.py) and the fused device step
(persia_tpu/parallel/cached_train.py), and owns the async write-back of
evicted rows to the parameter server. TrainCtx delegates here when
``device_cache_capacity`` is set.

Consistency model (documented trade, bounded like the reference's
staleness-based hybrid algorithm): cached rows train exclusively on
device; the PS copy of a cached sign is stale until the row is evicted
(write-back) or ``flush_all`` runs (eval/checkpoint entry points call
it). A cache miss reads the victim buffer first, so an evicted row
re-entering the cache never loses its in-flight update. Single-trainer
only: replicated per-trainer caches would fork hot rows' optimizer
state across trainers with no reconciliation. A device MESH is fine —
the cache is then ONE logical array row-sharded over the mesh by GSPMD
(see cached_train._row_sharding): still a single program, a single
writer, and per-device HBM that scales down with the device count.

Single-CONTROLLER only, enforced upstream: ``TrainCtx._ensure_cache``
raises NotImplementedError when ``jax.process_count() > 1``. On a
multi-process mesh the cache arrays' rows live on remote hosts this
process cannot address for miss imports / eviction write-backs, and
each process would run its own divergent sign->slot mapper. Lifting
this needs per-process row ownership (mapper sharded by
``jax.process_index``), not just GSPMD on the arrays.
"""

import itertools
import queue
import threading
from typing import List, Tuple

import numpy as np

from persia_tpu import tracing
from persia_tpu.logger import get_logger
from persia_tpu.parallel.cached_train import pad_to_bucket
from persia_tpu.worker.device_cache import VictimBuffer, make_sign_slot_map

logger = get_logger(__name__)

_BUCKETS = (64, 256, 1024, 4096, 16384, 65536)

# monotone id per DeviceCacheEngine in this process (metric label)
_ENGINE_SEQ = itertools.count()


class DeviceCacheEngine:
    def __init__(self, worker, capacity: int, num_slots: int, dim: int,
                 acc_init: float, mesh=None, sqrt_scaling=None,
                 admission: str = None):
        from persia_tpu import knobs

        self.worker = worker
        self.capacity = int(capacity)
        self.num_slots = int(num_slots)
        self.dim = int(dim)
        self.acc_init = float(acc_init)
        self.mesh = mesh
        # per-slot sqrt-scaling flags (bag mode only; see prepare_bags)
        self.sqrt_scaling = list(sqrt_scaling or [])
        # admission policy of the HBM tier: "lru" (legacy) or "hotness"
        # (frequency-gated TieredSignSlotMap; PERSIA_TIER_ADMIT)
        self.admission = admission or knobs.get("PERSIA_TIER_ADMIT")
        self.mapper = make_sign_slot_map(capacity, self.admission)
        self.victims = VictimBuffer()
        from persia_tpu.parallel.cached_train import init_cache_arrays

        self.cache_vals, self.cache_acc = init_cache_arrays(
            capacity, dim, acc_init, mesh=mesh)
        self._flush_q: "queue.Queue" = queue.Queue()
        self._flush_token = 0
        self._flush_err: List[BaseException] = []
        self._flush_thread = threading.Thread(
            target=self._flush_loop, daemon=True,
            name="device-cache-flush")
        self._flush_thread.start()
        self.wire_bytes_saved = 0  # vs the packed upload+download path
        # registry twins of the mapper/write-back counters, so the
        # trainer sidecar (and the fleet federation scraping it) can
        # watch tier-ladder health; bumped by deltas once per batch —
        # the per-sign hot path never touches a locked counter
        from persia_tpu.metrics import default_registry

        reg = default_registry()
        # engine-identity label: two live engines in one process (A/B
        # benches, multi-ctx tests) must not share series — a blended
        # hit ratio and a last-writer-wins resident gauge would lie to
        # the hit-collapse SLO
        lbl = {"dim": str(dim), "engine": str(next(_ENGINE_SEQ))}
        self._m_probes = reg.counter(
            "device_cache_probes_total", lbl,
            help_text="sign positions probed against the device cache "
                      "(hits + misses) — the hit-rate denominator")
        self._m_hits = reg.counter(
            "device_cache_hits_total", lbl,
            help_text="device-cache hits (rows served from HBM, no "
                      "host<->device or PS traffic)")
        self._m_misses = reg.counter(
            "device_cache_misses_total", lbl,
            help_text="device-cache misses (rows imported from the PS "
                      "tier / victim buffer)")
        self._m_evictions = reg.counter(
            "device_cache_evictions_total", lbl,
            help_text="rows evicted from the device cache (each queues "
                      "one PS write-back)")
        self._m_promotions = reg.counter(
            "device_cache_promotions_total", lbl,
            help_text="window->protected promotions of the "
                      "hotness-admitted mapper (0 under LRU admission)")
        self._m_writebacks = reg.counter(
            "device_cache_writeback_rows_total", lbl,
            help_text="rows written back to the PS tier (eviction "
                      "flushes + flush_all)")
        self._m_resident = reg.gauge(
            "device_cache_resident_rows", lbl,
            help_text="signs currently resident in the device cache")
        self._counted = (0, 0, 0, 0)  # hits/misses/evictions/promotions

    def _publish_counters(self):
        """Delta the mapper's plain-int counters into their registry
        twins (once per batch, after assign)."""
        m = self.mapper
        h, mi, ev, pr = (m.hits, m.misses, m.evictions,
                         getattr(m, "promotions", 0))
        ph, pm, pe, pp = self._counted
        self._counted = (h, mi, ev, pr)
        if h - ph:
            self._m_hits.inc(h - ph)
        if mi - pm:
            self._m_misses.inc(mi - pm)
        if (h - ph) + (mi - pm):
            self._m_probes.inc((h - ph) + (mi - pm))
        if ev - pe:
            self._m_evictions.inc(ev - pe)
        if pr - pp:
            self._m_promotions.inc(pr - pp)
        self._m_resident.set(len(m))

    def _map(self, flat_signs):
        """Signs -> cache slots through the mapper, counters published:
        the mapper's part of a step's host time."""
        with tracing.span("cache/map", signs=len(flat_signs)) as sp:
            res = self.mapper.assign(flat_signs)
            self._publish_counters()
            sp.tag(unique=int(res.n_unique), misses=len(res.miss_pos))
        return res

    # --- per-batch host work --------------------------------------------

    def prepare(self, id_type_features) -> Tuple[
            np.ndarray, np.ndarray, np.ndarray, np.ndarray,
            np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Map this batch's signs and fetch its miss rows.

        Returns (slot_idx (B,S) i32, cold_idx (Mpad,) i32, cold_vals
        (Mpad, D) f32, cold_acc (Mpad, D) f32, evicted_signs (Mpad,)
        u64, evicted_mask (Mpad,) bool, inverse (B*S,) i32,
        unique_slots (B*S,) i32). Runs on the ordered training path —
        batch order IS the LRU order.
        """
        # single-id slots: f.signs is exactly one sign per sample (the
        # ctx-level guard verified this before building the engine)
        signs = np.stack([f.signs for f in id_type_features], axis=1)
        batch, num_slots = signs.shape
        flat_signs = signs.reshape(-1)
        res = self._map(flat_signs)
        # tail past the distinct count is uninitialized: point it at the
        # dummy slot so the device update's pad rows are inert
        unique_slots = res.unique_slots
        unique_slots[res.n_unique:] = self.capacity
        slot_idx = res.slots.reshape(batch, num_slots)
        (cold_idx, cold_vals, cold_acc, evicted_signs, evicted_mask,
         mpad) = self._miss_import(flat_signs, res)
        # bookkeeping: what the packed path would have moved for this
        # batch (bf16 both ways) minus what the cached path moves
        packed = batch * num_slots * self.dim * 2 * 2
        moved = (slot_idx.nbytes + cold_idx.nbytes + cold_vals.nbytes
                 + cold_acc.nbytes + (2 * mpad * self.dim * 4))
        self.wire_bytes_saved += max(0, packed - moved)
        return (slot_idx, cold_idx, cold_vals, cold_acc, evicted_signs,
                evicted_mask, res.inverse, unique_slots)

    def prepare_bags(self, id_type_features) -> tuple:
        """Multi-id variant of :meth:`prepare` for summed bag slots.

        Flattens every (sample, slot) bag into one position list
        (slot-major), maps it through the same LRU assign, and returns
        (flat_slot_idx (Lpad,) i32, seg (Lpad,) i32, scale (B, S) f32,
        cold_idx, cold_vals, cold_acc, evicted_signs, evicted_mask,
        inverse (Lpad,) i32, unique_slots (Lpad,) i32) for
        ``make_cached_bag_train_step``. Pad positions carry
        seg == B*S (the trash bag row) and the dummy slot."""
        batch = id_type_features[0].batch_size
        num_slots = len(id_type_features)
        sign_parts, seg_parts, counts = [], [], []
        for s, f in enumerate(id_type_features):
            off = f.offsets.astype(np.int64)
            cnt = np.diff(off)
            counts.append(cnt)
            sign_parts.append(f.signs)
            seg_parts.append(
                np.repeat(np.arange(batch, dtype=np.int64) * num_slots + s,
                          cnt))
        flat_signs = np.concatenate(sign_parts).astype(np.uint64)
        seg = np.concatenate(seg_parts)
        n = len(flat_signs)
        res = self._map(flat_signs)
        lpad = pad_to_bucket(max(n, 1), _BUCKETS)
        flat_slot_idx = np.full(lpad, self.capacity, np.int32)
        flat_slot_idx[:n] = res.slots
        seg_pad = np.full(lpad, batch * num_slots, np.int32)
        seg_pad[:n] = seg
        # pad inverse entries add the (zero) trash-row grad to distinct
        # index 0 — adding zeros is inert
        inverse = np.zeros(lpad, np.int32)
        inverse[:n] = res.inverse
        unique_slots = np.full(lpad, self.capacity, np.int32)
        unique_slots[:res.n_unique] = res.unique_slots[:res.n_unique]
        # per-(sample, slot) sqrt scaling, matching the middleware's
        # 1/sqrt(max(bag size, 1)) (worker/middleware.py)
        scale = np.ones((batch, num_slots), np.float32)
        for s in range(num_slots):
            if self.sqrt_scaling and self.sqrt_scaling[s]:
                scale[:, s] = 1.0 / np.sqrt(
                    np.maximum(counts[s], 1).astype(np.float32))
        (cold_idx, cold_vals, cold_acc, evicted_signs, evicted_mask,
         mpad) = self._miss_import(flat_signs, res)
        packed = batch * num_slots * self.dim * 2 * 2
        moved = (flat_slot_idx.nbytes + seg_pad.nbytes + scale.nbytes
                 + cold_idx.nbytes + cold_vals.nbytes + cold_acc.nbytes
                 + (2 * mpad * self.dim * 4))
        self.wire_bytes_saved += max(0, packed - moved)
        return (flat_slot_idx, seg_pad, scale, cold_idx, cold_vals,
                cold_acc, evicted_signs, evicted_mask, inverse,
                unique_slots)

    def _miss_import(self, flat_signs, res):
        """Fetch this batch's miss rows (victim buffer first, then PS),
        bucket-padded. Returns (cold_idx, cold_vals, cold_acc,
        evicted_signs, evicted_mask, mpad)."""
        with tracing.span("cache/miss_import") as sp:
            slots, miss_pos, evicted, emask = (res.slots, res.miss_pos,
                                               res.evicted_signs,
                                               res.evicted_mask)
            miss_signs = flat_signs[miss_pos]
            m = len(miss_signs)
            mpad = pad_to_bucket(max(m, 1), _BUCKETS)
            sp.tag(rows=m, bucket=mpad)
            cold_idx = np.full(mpad, self.capacity, np.int32)  # pad -> dummy
            cold_vals = np.zeros((mpad, self.dim), np.float32)
            cold_acc = np.full((mpad, self.dim), self.acc_init, np.float32)
            evicted_signs = np.zeros(mpad, np.uint64)
            evicted_mask = np.zeros(mpad, bool)
            if m:
                cold_idx[:m] = slots[miss_pos]
                evicted_signs[:m] = evicted
                evicted_mask[:m] = emask
                # victim buffer first: an evicted row still in flight is the
                # authoritative copy (the PS write-back may not have landed).
                # Entries are (ev_vals, ev_acc, row) with possibly-device
                # arrays; np.asarray blocks until the step that produced
                # them finished, so the value read here is never stale.
                need_ps = []
                for i, s in enumerate(miss_signs):
                    v = self.victims.take(int(s))
                    if v is not None:
                        vvals, vacc, row = v
                        cold_vals[i] = np.asarray(vvals)[row]
                        cold_acc[i] = np.asarray(vacc)[row]
                    else:
                        need_ps.append(i)
                if need_ps:
                    idx = np.asarray(need_ps)
                    vals, state = self.worker.lookup_rows_with_state(
                        miss_signs[idx], self.dim,
                        default_state=self.acc_init)
                    cold_vals[idx] = vals
                    if state.shape[1] == self.dim:
                        cold_acc[idx] = state
                    # (space != dim would mean a non-matching optimizer; the
                    # ctx-level guard rejects that before the engine exists)
            return (cold_idx, cold_vals, cold_acc, evicted_signs,
                    evicted_mask, mpad)

    def finish(self, evicted_signs: np.ndarray, evicted_mask: np.ndarray,
               ev_vals, ev_acc) -> None:
        """Queue evicted rows for async PS write-back. ``ev_vals`` /
        ``ev_acc`` may be jax device arrays; the d2h materialization
        happens on the flush thread. The mask (not sign truthiness)
        selects real evictions — sign 0 is a legal sign."""
        if self._flush_err:
            raise self._flush_err[0]
        real = list(np.nonzero(evicted_mask)[0])
        with tracing.span("cache/finish", evicted=len(real)):
            if not real:
                return
            self._flush_token += 1
            token = self._flush_token
            for i in real:
                # the buffered entry holds the device arrays themselves:
                # a miss racing the write-back materializes its row
                # directly, so there is no window where the PS copy
                # (stale) is the only readable one
                self.victims.put(int(evicted_signs[i]),
                                 (ev_vals, ev_acc, i), token=token)
            self._flush_q.put((token, evicted_signs, real, ev_vals, ev_acc,
                               tracing.current_context()))

    # --- write-back -------------------------------------------------------

    def _flush_loop(self):
        while True:
            job = self._flush_q.get()
            if job is None:
                self._flush_q.task_done()
                return
            try:
                self._flush_job(*job)
            except BaseException as e:  # surfaced on the next finish()
                self._flush_err.append(e)
            finally:
                self._flush_q.task_done()

    def _flush_job(self, token, evicted_signs, real, ev_vals, ev_acc,
                   tctx):
        # joins the evicting step's trace where one was propagated
        kw = {"ctx": tctx} if tctx is not None else {}
        with tracing.span("cache/writeback", **kw) as sp:
            vals = np.asarray(ev_vals)  # d2h here, off the training thread
            acc = np.asarray(ev_acc)
            todo_signs, todo_vecs = [], []
            for i in real:
                sign = int(evicted_signs[i])
                # token-matched PEEK (no removal yet): absent or different
                # token => the miss path reclaimed the row (the cache copy is
                # authoritative again) or a newer eviction owns the sign —
                # either way writing our older value would clobber fresher
                # state, so skip.
                if self.victims.peek_if(sign, token) is None:
                    continue
                todo_signs.append(sign)
                todo_vecs.append(np.concatenate([vals[i], acc[i]]))
            sp.tag(rows=len(todo_signs))
            if todo_signs:
                self.worker.set_rows(
                    np.asarray(todo_signs, np.uint64),
                    np.stack(todo_vecs), self.dim)
                self._m_writebacks.inc(len(todo_signs))
            # remove only AFTER the PS write landed: a miss racing the write
            # must keep finding the pending entry, otherwise it would read
            # the stale pre-write PS row. A miss that took the entry mid-
            # write is also fine — the PS got the same value, and the cache
            # copy stays authoritative.
            for sign in todo_signs:
                self.victims.take_if(sign, token)

    def flush_all(self) -> int:
        """Write every cached row (+ the victim buffer) back to the PS.
        Called before eval/checkpoint so the PS is authoritative. The
        cache stays valid for continued training. Returns rows written."""
        self._drain_flush_queue()
        signs, slots = self.mapper.signs_and_slots()
        n = len(signs)
        if n:
            vals = np.asarray(self.cache_vals)[slots]
            acc = np.asarray(self.cache_acc)[slots]
            vecs = np.concatenate([vals, acc], axis=1)
            self.worker.set_rows(signs, vecs, self.dim)
            self._m_writebacks.inc(n)
        while True:
            item = self.victims.pop_any()
            if item is None:
                break
            # payloads are always (ev_vals, ev_acc, row) triples; after
            # the queue drain this loop is normally empty, but a row left
            # behind (e.g. flush after close()) must still write back
            sign, (vvals, vacc, row) = item
            vec = np.concatenate(
                [np.asarray(vvals)[row], np.asarray(vacc)[row]])
            self.worker.set_rows(
                np.asarray([sign], np.uint64), vec[None, :], self.dim)
            self._m_writebacks.inc()
            n += 1
        return n

    def invalidate(self) -> None:
        """Drop every cached row WITHOUT writing back — checkpoint
        restore: the cache predates the loaded values, so both serving
        further hits from it and flushing it would clobber the restore.
        Queued write-backs are drained first and their PS writes land
        BEFORE the restore overwrites them (load happens after this
        returns), which is the correct order."""
        self._drain_flush_queue()
        while self.victims.pop_any() is not None:
            pass
        self.mapper = make_sign_slot_map(self.capacity, self.admission)
        self._counted = (0, 0, 0, 0)
        self._m_resident.set(0)
        from persia_tpu.parallel.cached_train import init_cache_arrays

        self.cache_vals, self.cache_acc = init_cache_arrays(
            self.capacity, self.dim, self.acc_init, mesh=self.mesh)

    def _drain_flush_queue(self):
        """Block until queued write-backs complete (order matters: a
        flush_all snapshot must not be overwritten by an older queued
        eviction landing later). task_done bookkeeping in _flush_loop
        makes join() cover the in-progress job too."""
        self._flush_q.join()
        if self._flush_err:
            raise self._flush_err[0]

    def close(self):
        """Stop the flush thread (TrainCtx.__exit__). The engine's state
        (cache arrays, mapper) stays valid; ensure_open() restarts the
        thread if the ctx is re-entered."""
        if self._flush_thread.is_alive():
            self._flush_q.put(None)
            self._flush_thread.join(timeout=30)

    def ensure_open(self):
        if not self._flush_thread.is_alive():
            # a recorded flush error belongs to the previous life of the
            # ctx (it was raised at — or superseded by — exit); keeping
            # it would make every finish()/flush of the re-entered ctx
            # re-raise a stale, already-surfaced exception forever.
            # But if the ctx exited on an UNRELATED exception, the exit
            # path skipped flush_device_cache and nothing ever raised
            # this — write-backs were lost silently. Leave a trace.
            if self._flush_err:
                logger.warning(
                    "device-cache: discarding %d unraised write-back "
                    "error(s) from the previous ctx life (first: %r) — "
                    "PS updates queued before the abnormal exit were "
                    "lost", len(self._flush_err), self._flush_err[0])
            self._flush_err.clear()
            self._flush_thread = threading.Thread(
                target=self._flush_loop, daemon=True,
                name="device-cache-flush")
            self._flush_thread.start()

    @property
    def hit_rate(self) -> float:
        return self.mapper.hit_rate
