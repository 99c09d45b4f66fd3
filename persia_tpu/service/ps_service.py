"""Embedding parameter-server service + its RPC client.

Service binary for one PS replica (reference:
src/bin/persia-embedding-parameter-server.rs + the RPC surface of
embedding_parameter_service/mod.rs:491-593). Wraps the fastest available
store backend (C++ native, numpy fallback) behind the TCP RPC; registers
itself with the coordinator; in Infer mode loads the initial sparse
checkpoint at boot (reference: bin rs:108-116).

Run: ``python -m persia_tpu.service.ps_service --port 0 --replica-index 0
--replica-size 2 [--coordinator 127.0.0.1:23333]``

``PsClient`` exposes the in-process holder interface (configure /
register_optimizer / lookup / update_gradients / ...), so an
:class:`~persia_tpu.worker.worker.EmbeddingWorker` runs over the network
without code changes.
"""

import argparse
import os
import threading
import time
from typing import List, Optional

import msgpack
import numpy as np

from persia_tpu import knobs
from persia_tpu import faults, tracing
from persia_tpu.logger import get_default_logger
from persia_tpu.rpc import (
    CircuitBreaker,
    RpcCircuitOpen,
    RpcClient,
    RpcServer,
    pack_arrays,
    pack_arrays_sg,
    tcp_probe,
    unpack_arrays,
)
from persia_tpu.service.coordinator import ROLE_PS, CoordinatorClient

_logger = get_default_logger(__name__)


class _WriteGate:
    """Generation-counted barrier over the PS write handlers.

    Every write (gradient update, row write, training lookup — they
    create rows) enters the CURRENT generation and exits when applied.
    ``drain_prior`` flips the generation and waits for the old one to
    empty: after it returns, every write that began before the flip is
    fully visible in the holder. ``reshard_begin`` uses it between
    arming capture and snapshotting, closing the race where an
    in-flight pre-arm write lands in a shard the snapshot already
    serialized — invisible to both the copy and the capture set, i.e.
    a silently lost update. Cost on the hot path: one uncontended
    lock pair per write handler."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        # per-generation in-flight counts (pruned when they hit zero):
        # a dict, not a two-slot parity array, so a drain that TIMED
        # OUT on a wedged write leaves that write's generation visible
        # to the next drain instead of aliasing it into the current one
        self._counts: Dict[int, int] = {}
        self._gen = 0

    def enter(self) -> int:
        with self._lock:
            g = self._gen
            self._counts[g] = self._counts.get(g, 0) + 1
        return g

    def exit(self, g: int):
        with self._lock:
            self._counts[g] -= 1
            if self._counts[g] == 0:
                del self._counts[g]
                self._cond.notify_all()

    def drain_prior(self, timeout: float = 10.0):
        """Bump the generation; wait until EVERY write of an earlier
        generation has applied. One caller at a time (reshard_begin
        holds the reshard lock)."""
        with self._lock:
            self._gen += 1
            cur = self._gen
            deadline = time.monotonic() + timeout
            while any(g < cur for g in self._counts):
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError(
                        "pre-arm writes did not drain before the "
                        "reshard snapshot")
                self._cond.wait(left)


class _ReshardState:
    """Donor-side state of one in-flight slot migration: the moving
    slot mask, the write-capture set, the snapshot stream, and the
    freeze barrier. One per replica at a time (reshard_begin refuses a
    second); the hot-path cost while NO migration runs is a single
    ``self._reshard is None`` test per handler."""

    def __init__(self, slots, num_slots: int, epoch: int,
                 mig_id: Optional[str] = None,
                 token: Optional[tuple] = None,
                 lease_sec: Optional[float] = None):
        self.num_slots = int(num_slots)
        self.epoch = int(epoch)
        # fencing identity: which migration attempt owns this state
        # (None on both = a legacy unfenced controller)
        self.mig_id = mig_id
        self.token = (int(token[0]), int(token[1])) if token else None
        self.mask = np.zeros(self.num_slots, dtype=bool)
        self.mask[np.asarray(sorted(set(int(s) for s in slots)),
                             dtype=np.int64)] = True
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.frozen = False  # plain-bool fast reads are GIL-atomic
        self.frozen_at = 0.0  # monotonic stamp of the freeze
        self.inflight = 0
        self.captured: set = set()
        self.captured_total = 0
        self.snapshot_rows: List = []
        self.extract_pos = 0
        # donor self-healing lease: every controller RPC touching this
        # state renews it; expiry means the controller stopped
        # heartbeating (died, partitioned) and the donor auto-thaws —
        # discard capture, unfreeze, bounce back to the old epoch —
        # rather than serving a frozen-forever shard. 0 disables.
        if lease_sec is None:
            lease_sec = float(
                knobs.get("PERSIA_RESHARD_FREEZE_LEASE_SEC"))
        self.lease_sec = float(lease_sec)
        self.lease_deadline = (time.monotonic() + self.lease_sec
                               if self.lease_sec > 0 else float("inf"))

    def touch(self):
        """Renew the controller lease (called by every fence-valid
        reshard RPC that reaches this state)."""
        if self.lease_sec > 0:
            self.lease_deadline = time.monotonic() + self.lease_sec

    def lease_expired(self) -> bool:
        return time.monotonic() >= self.lease_deadline

    def hits(self, signs: np.ndarray) -> Optional[np.ndarray]:
        """The subset of ``signs`` living in a moving slot (None when
        disjoint — the overwhelmingly common case)."""
        from persia_tpu.hashing import farmhash64_np

        s = np.ascontiguousarray(signs, dtype=np.uint64)
        if len(s) == 0:
            return None
        slot = (farmhash64_np(s)
                % np.uint64(self.num_slots)).astype(np.int64)
        hit = self.mask[slot]
        return s[hit] if hit.any() else None

    def enter_write(self, signs: np.ndarray) -> Optional[np.ndarray]:
        """Gate one write batch: None when it touches no moving slot;
        otherwise registers the in-flight write (for the freeze
        barrier) and returns the signs to capture on exit. A frozen
        state bounces the writer with the typed routing_stale error
        the worker's re-split path understands."""
        hit = self.hits(signs)
        if hit is None:
            return None
        with self._lock:
            if self.frozen:
                from persia_tpu.routing import STALE_PREFIX
                from persia_tpu.rpc import RpcError

                raise RpcError(f"{STALE_PREFIX}{self.epoch}")
            self.inflight += 1
        return hit

    def exit_write(self, hit: np.ndarray):
        with self._lock:
            self.captured.update(int(x) for x in hit)
            self.captured_total += len(hit)
            self.inflight -= 1
            if self.inflight == 0:
                self._cond.notify_all()

    def freeze(self, timeout: float = 5.0):
        """Stop admitting writes for the moving slots and wait out the
        writes already past the gate — after this returns, the final
        capture drain reads definitive row state. Idempotent: a
        repeated freeze (retry after an ambiguous timeout) re-waits the
        barrier, which is already empty."""
        with self._lock:
            if not self.frozen:
                self.frozen = True
                self.frozen_at = time.monotonic()
            deadline = time.monotonic() + timeout
            while self.inflight > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError(
                        "reshard freeze: in-flight writes did not "
                        "settle within the barrier timeout")
                self._cond.wait(left)

    def drain_captured(self) -> set:
        with self._lock:
            out, self.captured = self.captured, set()
        return out


# numeric encodings for the constant-per-process path gauges (the
# fleet scraper compares them across replicas to flag skew)
SIMD_PATH_CODES = {"scalar": 0, "avx2": 1, "neon": 2}
DISPATCH_MODE_CODES = {"serial": 0, "pool": 1, "native": 2}


class ShardParallelDispatcher:
    """Executes holder lookups/updates in parallel across the holder's
    INTERNAL shards (thread pool sized to ``num_internal_shards``,
    capped at the host's core count — extra workers on a small host are
    pure scheduling tax).

    The split buckets shards with the same ``internal_shard_of`` hash
    both store backends use, so sub-calls touch DISJOINT internal
    shards — per-shard mutexes never contend across pool threads, and
    every per-shard operation sequence is identical to the serial call
    (duplicates of a sign land in one sub-batch in original order;
    per-shard LRU/eviction order is unchanged — the parity tests pin
    this). Effective with the native C++ holder, whose ctypes calls
    release the GIL; the pure-Python holder computes under the GIL, so
    it falls back to the plain serial call (``force=True`` overrides,
    for the parity tests).

    Backends that expose ``parallel_info()``/``set_parallel()`` (the
    tuning-capable native .so) get "native" mode instead: the store's
    own parallel_shards is tuned down to MIN_PARALLEL at construction,
    so lookup/update stay ONE foreign call — the GIL is released across
    the whole request and the store fans out over its internal shards
    by itself. No Python pool means no per-core dispatch tax, so this
    mode engages on any host (the old ``cpus >= 4`` floor only guarded
    pool.map overhead). The thread pool remains for backends that lack
    the tuning ABI (pre-SIMD .so — detected by the capability probe,
    not the class name) and for ``force=True`` parity tests that pin
    the split/scatter semantics.
    """

    # below this many signs the split/scatter overhead beats the win;
    # native mode tunes store.h parallel_shards to this same threshold
    MIN_PARALLEL = 512
    # legacy fallback when the .so predates ptps_get_parallel and its
    # internal config cannot be probed: store.h parallel_shards used to
    # hard-code this engage batch size with min(8, hw) threads
    NATIVE_INTERNAL_N = 4096
    NATIVE_INTERNAL_THREADS = 8

    def __init__(self, holder, enabled: Optional[bool] = None,
                 force: bool = False):
        self.holder = holder
        self.force = force
        n = int(getattr(holder, "num_internal_shards", 1))
        self._releases_gil = bool(getattr(holder, "releases_gil", False))
        if enabled is None:
            enabled = self._releases_gil
        cpus = os.cpu_count() or 1
        self._workers = min(n, max(cpus, 1))
        # capability probe: a tuning-capable native backend reports its
        # internal parallel_shards config (and accepts overrides); a
        # pre-SIMD .so or the pure-Python holder reports None and
        # negotiates down to the legacy pool/serial behavior
        self._native_par = None
        probe = getattr(holder, "parallel_info", None)
        if callable(probe) and not force:
            try:
                self._native_par = probe()
            except Exception:
                self._native_par = None
        want = bool(knobs.get("PERSIA_PS_SHARD_PARALLEL"))
        self.mode = "serial"
        self._pool = None
        if (self._native_par is not None and enabled and n > 1 and want):
            # native-internal mode: one GIL-released call per request;
            # the store fans out internally from MIN_PARALLEL signs.
            # Hosts beyond the store's 8-thread auto cap get an
            # explicit thread count so big machines are not left idle.
            threads = 0 if cpus <= 8 else min(n, cpus)
            try:
                holder.set_parallel(threads, self.MIN_PARALLEL)
                self._native_par = probe()
            except Exception:
                pass
            self.mode = "native"
            self.enabled = True
        else:
            # a 2-core host is already saturated by thread-per-
            # connection request concurrency; pool.map dispatch there
            # costs more than the split wins (measured: +26 ms/batch at
            # bs=256 on 2 cores), so the pool needs headroom to engage
            self.enabled = bool(
                (force or enabled)
                and n > 1
                and (force or cpus >= 4)
                and want
            )
            if self.enabled:
                from concurrent.futures import ThreadPoolExecutor

                self.mode = "pool"
                self._pool = ThreadPoolExecutor(
                    max_workers=self._workers,
                    thread_name_prefix="ps-shard")

    def info(self) -> dict:
        """Health/metrics snapshot: how this replica dispatches."""
        doc = {"mode": self.mode, "enabled": self.enabled,
               "workers": self._workers}
        if self._native_par is not None:
            doc["native_threads"] = int(self._native_par["threads"])
            doc["native_min_batch"] = int(self._native_par["min_batch"])
        return doc

    def _engage(self, n_signs: int) -> bool:
        if not self.enabled or n_signs < self.MIN_PARALLEL:
            return False
        if self.mode == "native":
            # the tuned store parallelizes inside the single foreign
            # call — splitting here would serialize it behind pool.map
            return False
        if self.force:
            return True
        if self._releases_gil:
            # the native store's own parallel_shards already covers
            # this batch with as many threads as this host has —
            # splitting here would only disable it and add dispatch
            # overhead. Probed config when the backend reports one,
            # legacy constants for an old .so.
            if self._native_par is not None:
                nat_n = int(self._native_par["min_batch"])
                nat_t = int(self._native_par["threads"])
            else:
                nat_n = self.NATIVE_INTERNAL_N
                nat_t = self.NATIVE_INTERNAL_THREADS
            if n_signs >= nat_n and self._workers <= nat_t:
                return False
        return True

    def _shard_buckets(self, signs: np.ndarray) -> List[np.ndarray]:
        from persia_tpu.ps.rng import internal_shard_of

        n_shards = self.holder.num_internal_shards
        shard_ids = internal_shard_of(signs, n_shards)
        # contiguous shard-id ranges -> one bucket per pool worker;
        # stable sort keeps duplicate signs in original order inside
        # their bucket — sequential-duplicate semantics hold
        buckets = (shard_ids * self._workers) // n_shards
        order = np.argsort(buckets, kind="stable")
        sorted_ids = buckets[order]
        cuts = np.nonzero(np.diff(sorted_ids))[0] + 1
        return np.split(order, cuts)

    def lookup(self, signs: np.ndarray, dim: int,
               training: bool) -> np.ndarray:
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        if not self._engage(len(signs)):
            return self.holder.lookup(signs, dim, training)
        groups = self._shard_buckets(signs)
        if len(groups) <= 1:
            return self.holder.lookup(signs, dim, training)
        out = np.empty((len(signs), dim), dtype=np.float32)
        # pool threads have no thread-local trace context; capture the
        # handler span here so per-shard sub-lookups parent to it
        tctx = tracing.current_context()

        def run(ib):
            i, sel = ib
            with tracing.span("ps/shard_lookup", ctx=tctx, bucket=i,
                              n=len(sel)):
                out[sel] = self.holder.lookup(signs[sel], dim, training)

        # pool.map raises the first sub-call error after all complete
        list(self._pool.map(run, enumerate(groups)))
        return out

    def update_gradients(self, signs: np.ndarray, grads: np.ndarray,
                         dim: int):
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        grads = np.ascontiguousarray(grads, dtype=np.float32)
        if not self._engage(len(signs)):
            return self.holder.update_gradients(signs, grads, dim)
        groups = self._shard_buckets(signs)
        if len(groups) <= 1:
            return self.holder.update_gradients(signs, grads, dim)
        tctx = tracing.current_context()

        def run(ib):
            i, sel = ib
            with tracing.span("ps/shard_update", ctx=tctx, bucket=i,
                              n=len(sel)):
                self.holder.update_gradients(signs[sel], grads[sel], dim)

        list(self._pool.map(run, enumerate(groups)))

    def close(self):
        if self._pool is not None:
            self._pool.shutdown(wait=False)


class PsService:
    def __init__(self, holder, host: str = "127.0.0.1", port: int = 0,
                 inc_dumper=None, shard_parallel: Optional[bool] = None,
                 concurrent_streams: int = 8,
                 http_port: Optional[int] = None, inc_loader=None):
        self.holder = holder
        self.inc_dumper = inc_dumper
        # infer-side incremental loader (when this replica hot-loads
        # train-tier packets): referenced so /healthz and the health
        # RPC can report serving freshness alongside resident bytes
        self.inc_loader = inc_loader
        # concurrent_streams opts into the per-connection dispatch pool:
        # a multiplexing worker (tagged framing) gets out-of-order
        # completion, so one slow lookup never convoys the connection;
        # legacy blocking clients see the exact serial behavior
        self.server = RpcServer(host, port,
                                concurrent_streams=concurrent_streams)
        self._dispatch = ShardParallelDispatcher(holder,
                                                 enabled=shard_parallel)
        self._pack = pack_arrays_sg
        self.status = "Idle"  # Idle | Dumping | Loading | Failed (model mgr)
        self._status_lock = threading.Lock()
        s = self.server
        s.register("configure", self._configure)
        s.register("register_optimizer", self._register_optimizer)
        s.register("lookup", self._lookup)
        s.register("update_gradients", self._update_gradients)
        s.register("len", self._len)
        s.register("get_entry", self._get_entry)
        s.register("set_entry", self._set_entry)
        s.register("get_entries", self._get_entries)
        s.register("set_entries", self._set_entries)
        s.register("clear", self._clear)
        s.register("dump", self._dump)
        s.register("load", self._load)
        s.register("status", self._status)
        s.register("ready_for_serving", self._ready)
        # RPC twin of the sidecar's /healthz (the bench and capacity
        # tooling read resident bytes without scraping HTTP)
        s.register("health", self._health_rpc)
        # workload-telemetry snapshot (persia_tpu.hotness): answers the
        # disabled marker when sketches are unarmed, so callers need no
        # negotiation — and nobody calls it with telemetry off, keeping
        # the disabled wire byte-identical
        s.register("hotness", self._hotness_rpc)
        # live-resharding surface (persia_tpu.reshard drives it): slot
        # snapshot/extract on the donor, row install on the target,
        # capture drain + write freeze for the zero-lost-updates
        # cutover. Plain methods — nothing here rides the envelope, so
        # fleets that never reshard keep a byte-identical wire.
        self._reshard: Optional[_ReshardState] = None
        self._reshard_lock = threading.Lock()
        # sticky fencing watermark: the highest (epoch, attempt) token
        # any reshard RPC ever presented — survives the state it fenced
        # (a thawed/finished migration must still fence out its dead
        # controller's stragglers)
        self._reshard_fence = (0, 0)
        self._routing_epoch = 0
        self._wgate = _WriteGate()
        s.register("reshard_begin", self._reshard_begin)
        s.register("reshard_extract", self._reshard_extract)
        s.register("reshard_install", self._reshard_install)
        s.register("reshard_drain", self._reshard_drain)
        s.register("reshard_freeze", self._reshard_freeze)
        s.register("reshard_finish", self._reshard_finish)
        s.register("reshard_status", self._reshard_status)
        s.register("set_routing_epoch", self._set_routing_epoch)
        # __routing__ envelope rider (declared in ENVELOPE_EXTENSIONS):
        # acks routing-aware clients with this replica's epoch; legacy
        # clients never probe, probing clients of a legacy server get
        # "no such method" — negotiate-down both ways
        s.register("__routing__", lambda payload: msgpack.packb(
            {"epoch": self._routing_epoch}))
        # gradient-staleness accounting: one update-batch version
        # counter bumped per update RPC (two uncontended lock ops — the
        # same cost class as the server's stats lock). A telemetry-armed
        # client echoes the version its lookup saw back on its update
        # meta; the difference is the update's staleness in apply steps.
        self._ver_lock = threading.Lock()
        self._update_ver = 0
        # per-internal-shard resident-bytes gauges (every arena-era
        # backend; a pre-arena .so reports none) — refreshed on every
        # health read and before each /metrics render
        from persia_tpu.metrics import default_registry

        self._mem_gauges: List = []
        reg = default_registry()
        port_label = self.server.addr.rsplit(":", 1)[1]
        if hasattr(holder, "resident_bytes_per_shard"):
            self._mem_gauges = [
                reg.gauge("ps_resident_bytes",
                          {"server": port_label, "shard": str(i)})
                for i in range(holder.num_internal_shards)
            ]
        # arena slab accounting (both arena backends expose it): the
        # GC-pressure fix is only real if its failure mode — slab space
        # held by eviction-churned free slots — is observable, so the
        # fragmentation ratio rides the same refresh hook and a default
        # SLO rule (slos.arena_fragmentation_runaway) watches it
        self._arena_gauges = None
        if getattr(holder, "arena_stats", None) is not None:
            self._arena_gauges = {
                "slab_bytes": reg.gauge(
                    "ps_arena_slab_bytes", {"server": port_label},
                    help_text="bytes of allocated arena slabs (resident "
                              "rows + free slots + padding)"),
                "free_slots": reg.gauge(
                    "ps_arena_free_slots", {"server": port_label},
                    help_text="evicted row slots awaiting reuse in the "
                              "arena free lists"),
                "live_rows": reg.gauge(
                    "ps_arena_live_rows", {"server": port_label},
                    help_text="rows resident in the arena (excludes "
                              "the disk spill tier)"),
                "fragmentation_ratio": reg.gauge(
                    "ps_arena_fragmentation_ratio",
                    {"server": port_label},
                    help_text="free slots / allocated slots — slab "
                              "space held by eviction churn instead of "
                              "live rows (the arena never returns "
                              "slabs; a runaway ratio means capacity "
                              "planning should shrink the table or "
                              "restart the replica)"),
            }
        # kernel-path + dispatch gauges: constant-per-process codes so
        # /fleet/status (and any scraper) can flag a replica that fell
        # back to scalar kernels or negotiated shard-parallel dispatch
        # down to serial without parsing /healthz. simd: -1 no native
        # SIMD ABI | 0 scalar | 1 avx2 | 2 neon; dispatch: 0 serial |
        # 1 thread-pool | 2 native-internal.
        simd_name = getattr(holder, "simd_path", None)
        g_simd = reg.gauge(
            "ps_simd_path", {"server": port_label},
            help_text="native kernel path this replica selected "
                      "(-1 none/pre-SIMD .so, 0 scalar, 1 avx2, "
                      "2 neon) — scalar on an AVX2 host usually means "
                      "PERSIA_NATIVE_SIMD was forced down")
        g_simd.set(SIMD_PATH_CODES.get(simd_name, -1))
        g_disp = reg.gauge(
            "ps_dispatch_mode", {"server": port_label},
            help_text="shard-parallel dispatch mode (0 serial, "
                      "1 thread-pool, 2 native-internal GIL-free)")
        g_disp.set(DISPATCH_MODE_CODES.get(self._dispatch.mode, 0))
        # disk-tier gauges (spill-armed holders only)
        self._spill_gauges = None
        if getattr(holder, "spill", None) is not None:
            self._spill_gauges = {
                "spilled_rows": reg.gauge(
                    "ps_spill_resident_rows", {"server": port_label},
                    help_text="rows currently demoted to the disk "
                              "spill tier"),
                "spill_disk_bytes": reg.gauge(
                    "ps_spill_disk_bytes", {"server": port_label},
                    help_text="bytes of live spill packets on disk"),
                "spilled_rows_total": reg.gauge(
                    "ps_spill_demotions_total", {"server": port_label},
                    help_text="rows ever demoted RAM->disk (monotone)"),
                "spill_fault_ins_total": reg.gauge(
                    "ps_spill_fault_ins_total", {"server": port_label},
                    help_text="rows ever faulted disk->RAM (monotone)"),
                "spill_dropped_rows": reg.gauge(
                    "ps_spill_dropped_rows_total", {"server": port_label},
                    help_text="rows dropped with their packet when the "
                              "disk budget overflowed (monotone)"),
            }
        # donor-side migration observables: the frozen-slot age gauge is
        # what the reshard_frozen_slot_stuck SLO rule watches — a
        # controller that dies POST-freeze never trips the controller-
        # side reshard_stuck gauge, so the donor must report its own
        # wedged state; the lease counter records every self-healing
        # auto-thaw
        self._g_frozen_age = reg.gauge(
            "ps_frozen_slot_age_sec", {"server": port_label},
            help_text="seconds this replica's moving slots have been "
                      "write-frozen by an in-flight migration (0 when "
                      "not frozen) — a stuck value means the reshard "
                      "controller died post-freeze; the freeze lease "
                      "auto-thaws it")
        self._c_lease_expired = reg.counter(
            "ps_reshard_lease_expired_total", {"server": port_label},
            help_text="migrations this donor auto-thawed because the "
                      "controller stopped heartbeating within the "
                      "freeze lease")
        from persia_tpu.metrics import STEP_BUCKETS

        self._h_staleness = reg.histogram(
            "ps_gradient_staleness_steps", {"server": port_label},
            help_text="update batches applied between a telemetry-"
                      "armed client's lookup and its gradient's "
                      "apply (async-pipeline staleness, in steps)",
            buckets=STEP_BUCKETS)
        # load-signal gauges for the autopilot's scale decisions: ROW
        # volume, not RPC count — under the workers' all-to-all fanout
        # every request touches every replica, so per-replica RPC rate
        # is flat in replica count while rows/sec partitions with slot
        # ownership (the signal that actually responds to scaling and
        # to rebalancing). Pull-refreshed: the lookup path pays two
        # uncontended lock ops (the _ver_lock cost class); the rate
        # math runs per scrape in _refresh_mem_gauges.
        self._rows_lock = threading.Lock()
        self._rows_served = 0
        self._rows_rate_last: Optional[tuple] = None  # (t, rows)
        self._g_served_reqs = reg.gauge(
            "ps_served_requests_total", {"server": port_label},
            help_text="RPC requests this replica answered (monotone; "
                      "mirrors the health doc's served_rpcs so wire-"
                      "neutrality gates can read it from a scrape)")
        self._g_lookup_rows = reg.gauge(
            "ps_lookup_rows_total", {"server": port_label},
            help_text="embedding rows served by lookup RPCs (monotone) "
                      "— the load unit that scales with slot ownership")
        self._g_lookup_row_rate = reg.gauge(
            "ps_lookup_row_rate", {"server": port_label},
            help_text="lookup rows/sec over the interval between the "
                      "last two gauge refreshes (scrapes) — the "
                      "autopilot's sustained() scale signal and its "
                      "per-replica imbalance breakdown")
        # observability sidecar: /metrics + /healthz + /trace next to
        # the RPC socket (http_port=0 binds an ephemeral port; None
        # keeps the sidecar off — in-process test holders don't want a
        # listener per instance)
        from persia_tpu import obs_http

        self.http = obs_http.maybe_start(host, http_port, self._health,
                                         refresh_fn=self._refresh_mem_gauges,
                                         hotness_fn=self._hotness_snapshot)

    def _refresh_mem_gauges(self):
        self._maybe_expire_reshard()
        rs = self._reshard
        self._g_frozen_age.set(
            round(time.monotonic() - rs.frozen_at, 3)
            if rs is not None and rs.frozen else 0)
        if self._mem_gauges:
            for g, b in zip(self._mem_gauges,
                            self.holder.resident_bytes_per_shard()):
                g.set(b)
        if self._arena_gauges is not None:
            stats = self.holder.arena_stats()
            for key, g in self._arena_gauges.items():
                g.set(stats.get(key, 0))
        if self._spill_gauges is not None:
            stats = self.holder.spill_stats()
            for key, g in self._spill_gauges.items():
                g.set(stats.get(key, 0))
        # load gauges: totals every refresh; the rate only re-anchors
        # when at least 50ms passed, so a health probe landing right
        # after a scrape cannot collapse the window to noise
        t_now = time.monotonic()
        rate = None
        with self._rows_lock:
            rows = self._rows_served
            last = self._rows_rate_last
            if last is None:
                self._rows_rate_last = (t_now, rows)
            elif t_now - last[0] >= 0.05:
                self._rows_rate_last = (t_now, rows)
                rate = (rows - last[1]) / (t_now - last[0])
        self._g_lookup_rows.set(rows)
        self._g_served_reqs.set(self.server.health()["served_rpcs"])
        if rate is not None:
            self._g_lookup_row_rate.set(max(rate, 0.0))

    def _health_rpc(self, payload: bytes) -> bytes:
        return msgpack.packb(self._health())

    def _hotness_snapshot(self) -> dict:
        from persia_tpu import hotness as _hotness

        snap_fn = getattr(self.holder, "hotness_snapshot", None)
        return snap_fn() if snap_fn is not None else (
            _hotness.disabled_snapshot())

    def _hotness_rpc(self, payload: bytes) -> bytes:
        return msgpack.packb(self._hotness_snapshot())

    def _bump_update_ver(self) -> int:
        with self._ver_lock:
            self._update_ver += 1
            return self._update_ver

    def _current_update_ver(self) -> int:
        with self._ver_lock:
            return self._update_ver

    def _health(self) -> dict:
        doc = self.server.health()
        with self._status_lock:
            doc["model_manager_status"] = self.status
        doc["holder_entries"] = len(self.holder)
        doc["shard_parallel"] = self._dispatch.enabled
        # kernel-path + dispatch observables: which SIMD path the
        # native store selected (None for the python holder or a
        # pre-SIMD .so) and how this replica parallelizes requests —
        # /fleet/status flags replicas that fell back to scalar or
        # negotiated the dispatcher down
        doc["simd"] = getattr(self.holder, "simd_path", None)
        doc["dispatch"] = self._dispatch.info()
        # storage-policy observables: what precision this replica's rows
        # are stored at and how many data bytes are resident (split so
        # capacity planning can see the embedding-vs-state share); the
        # native holder has no byte accounting and reports -1
        doc["row_dtype"] = getattr(self.holder, "row_dtype", "fp32")
        doc["resident_bytes"] = getattr(self.holder, "resident_bytes", -1)
        doc["resident_emb_bytes"] = getattr(
            self.holder, "resident_emb_bytes", -1)
        doc["backend"] = type(self.holder).__name__
        # arena slab accounting (slab bytes, free slots, fragmentation)
        # for capacity tooling that reads health instead of /metrics
        arena_stats = getattr(self.holder, "arena_stats", None)
        if arena_stats is not None:
            stats = arena_stats()
            if stats:
                doc["arena"] = stats
        # workload telemetry: armed or not (the /hotness endpoint and
        # the hotness RPC carry the data itself), and the staleness
        # version counter for operators correlating update progress
        doc["hotness_enabled"] = getattr(self.holder, "hotness",
                                         None) is not None
        doc["update_version"] = self._current_update_ver()
        # elastic-tier observables: the published routing epoch and (only
        # while a migration runs) the donor-side capture/freeze state —
        # what /fleet/routing aggregates and the stuck-migration SLO
        # rule watches
        doc["routing_epoch"] = self._routing_epoch
        self._maybe_expire_reshard()
        rs = self._reshard
        if rs is not None:
            with rs._lock:
                doc["reshard"] = {
                    "frozen": rs.frozen,
                    "frozen_age_sec": (
                        round(time.monotonic() - rs.frozen_at, 3)
                        if rs.frozen else 0.0),
                    "pending_epoch": rs.epoch,
                    "mig_id": rs.mig_id,
                    "lease_sec": rs.lease_sec,
                    "captured": len(rs.captured),
                    "captured_total": rs.captured_total,
                    "snapshot_rows_left": len(rs.snapshot_rows),
                }
        # disk spill tier (the cold rung of the storage ladder): row/
        # byte/fault-in accounting for capacity planning and the tier
        # bench's per-level hit breakdown; absent when unarmed
        spill_stats = getattr(self.holder, "spill_stats", None)
        if spill_stats is not None:
            stats = spill_stats()
            if stats:
                doc["spill"] = stats
        if self.inc_loader is not None:
            # serving freshness: how far behind the train tier this
            # replica's hot-loaded rows run (scan-time delay; the
            # per-packet sign-to-servable distribution rides /metrics
            # as inc_update_freshness_lag_sec)
            doc["inc_update_last_delay_sec"] = round(
                self.inc_loader.last_delay_sec, 3)
            doc["inc_update_sec_since_last_apply"] = round(
                self.inc_loader.sec_since_last_apply, 3)
            doc["inc_update_packets_applied"] = (
                self.inc_loader.packets_applied)
        self._refresh_mem_gauges()
        # readiness (distinct from liveness): the sidecar's
        # /healthz?ready=1 returns 503 on False, so supervisors and k8s
        # readiness probes never route traffic to a replica that is
        # Loading/restoring or has not been re-armed with an optimizer
        doc["ready"] = (
            getattr(self.holder, "optimizer", True) is not None
            and doc["model_manager_status"] == "Idle"
        )
        return doc

    @property
    def addr(self):
        return self.server.addr

    def stop(self):
        self.server.stop()
        self._dispatch.close()
        if self.http is not None:
            self.http.stop()

    def _configure(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self.holder.configure(
            req["init_method"], req["init_params"],
            admit_probability=req["admit_probability"],
            weight_bound=req["weight_bound"],
            enable_weight_bound=req["enable_weight_bound"],
        )
        return b""

    def _register_optimizer(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self.holder.register_optimizer(
            req["config"],
            feature_index_prefix_bit=req["feature_index_prefix_bit"],
        )
        return b""

    def _lookup(self, payload: bytes) -> bytes:
        meta, (signs,) = unpack_arrays(payload)
        if faults._active:
            # chaos sites: delay == slow shard, die == kill mid-request
            faults.fire("ps.lookup", n=len(signs), dim=meta["dim"])
        # store-work span nests under the rpc/lookup handler span (same
        # thread): the one in-process parent->child chain a postmortem
        # bundle of THIS replica's ring can always validate. ctx= keeps
        # untraced requests untraced (no orphan roots) — same rule as
        # the shard dispatcher's sub-spans.
        # training lookups CREATE rows, so they are writes for the
        # migration capture and the write gate (eval lookups pass
        # untouched — reads are served from the donor through the
        # whole double-read window)
        rs = hit = None
        g = self._wgate.enter() if meta["training"] else None
        try:
            if meta["training"]:
                rs, hit = self._reshard_guard(signs, meta)
            with tracing.span("ps/lookup", ctx=tracing.current_context(),
                              n=len(signs), dim=meta["dim"]):
                out = self._dispatch.lookup(signs, meta["dim"],
                                            meta["training"])
        finally:
            if rs is not None and hit is not None:
                rs.exit_write(hit)
            if g is not None:
                self._wgate.exit(g)
        # row-volume accounting for the pull-refreshed load gauges
        with self._rows_lock:
            self._rows_served += len(signs)
        # telemetry-armed client asked ("hv" in the request meta) for
        # the holder's update version: it rides the response meta and
        # comes back on the client's update as "hver". Reply-only-when-
        # asked keeps every non-telemetry client's wire byte-identical.
        resp_extra = ({"hver": self._current_update_ver()}
                      if meta.get("hv") else {})
        if meta.get("resp") == "fp16" and self.server._enable_codec:
            # codec-negotiated client asked for half-precision rows:
            # the response meta names the encoding, so the client
            # decodes by what it GOT. The _enable_codec check keeps the
            # legacy-peer emulation lever honest — a codec-refusing
            # server answers fp32 on EVERY path, not just the
            # negotiated ones.
            from persia_tpu import wire_codec

            return self._pack({"codec": "fp16", **resp_extra},
                              [wire_codec.encode_fp16_rows(out)])
        # scatter-gather response (default): the (n, dim) result goes
        # to the socket without a tobytes() concatenation copy
        return self._pack(resp_extra, [out])

    def _update_gradients(self, payload: bytes) -> bytes:
        meta, arrays = unpack_arrays(payload)
        if meta.get("codec") == "int8":
            # int8 grads + per-row scales (codec-negotiated client;
            # the fp32 error-feedback residual stays client-side)
            from persia_tpu import wire_codec

            signs, q, scales = arrays
            grads = wire_codec.dequantize_int8_rows(q, scales)
        else:
            signs, grads = arrays
        if faults._active:
            faults.fire("ps.update", n=len(signs), dim=meta["dim"])
        rs = hit = None
        g = self._wgate.enter()
        try:
            rs, hit = self._reshard_guard(signs, meta)
            with tracing.span("ps/update", ctx=tracing.current_context(),
                              n=len(signs), dim=meta["dim"]):
                self._dispatch.update_gradients(signs, grads, meta["dim"])
        finally:
            if rs is not None and hit is not None:
                rs.exit_write(hit)
            if g is not None:
                self._wgate.exit(g)
        ver = self._bump_update_ver()
        hver = meta.get("hver")
        if hver is not None:
            # updates applied since the client's lookup saw the holder
            # (this one excluded) — the per-replica gradient-staleness
            # distribution in steps
            self._h_staleness.observe(max(ver - 1 - int(hver), 0))
        if self.inc_dumper is not None:
            self.inc_dumper.commit(signs)
        return b""

    def _len(self, payload: bytes) -> bytes:
        return msgpack.packb({"len": len(self.holder)})

    def _get_entry(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        entry = self.holder.get_entry(req["sign"])
        if entry is None:
            return pack_arrays({"found": False, "dim": 0}, [])
        dim, vec = entry
        return pack_arrays({"found": True, "dim": dim}, [vec])

    def _set_entry(self, payload: bytes) -> bytes:
        meta, (vec,) = unpack_arrays(payload)
        rs = hit = None
        g = self._wgate.enter()
        try:
            rs, hit = self._reshard_guard(
                np.asarray([meta["sign"]], dtype=np.uint64), meta)
            self.holder.set_entry(meta["sign"], meta["dim"], vec)
        finally:
            if rs is not None and hit is not None:
                rs.exit_write(hit)
            self._wgate.exit(g)
        # a full-row write is an update: it joins the version stream
        # and the incremental-update log exactly like a gradient apply,
        # so checkpoint replay and train->serve sync see one logical
        # table whether a row trained PS-side or device-side
        self._bump_update_ver()
        if self.inc_dumper is not None:
            self.inc_dumper.commit(
                np.asarray([meta["sign"]], dtype=np.uint64))
        return b""

    def _get_entries(self, payload: bytes) -> bytes:
        """Batched entry read (value + opt state) — ONE round trip for
        the device cache's miss import instead of one per sign."""
        meta, (signs,) = unpack_arrays(payload)
        found, vecs = self.holder.get_entries(
            signs, meta["width"])
        return self._pack({}, [found.astype(np.uint8), vecs])

    def _set_entries(self, payload: bytes) -> bytes:
        meta, (signs, vecs) = unpack_arrays(payload)
        rs = hit = None
        g = self._wgate.enter()
        try:
            rs, hit = self._reshard_guard(signs, meta)
            self.holder.set_entries(
                signs, meta["dim"],
                vecs.reshape(len(signs), -1))
        finally:
            if rs is not None and hit is not None:
                rs.exit_write(hit)
            self._wgate.exit(g)
        # the device cache's eviction/flush write-back: versioned like
        # update_gradients (write-backs are ordered with gradient
        # applies in one stream) and committed to the inc-update log —
        # before this, rows that trained on device never reached
        # incremental packets, so crash replay and serving hot-load
        # silently missed them
        ver = self._bump_update_ver()
        if self.inc_dumper is not None:
            self.inc_dumper.commit(signs)
        if meta.get("wv"):
            # versioned write-back rider (reply-only-when-asked, like
            # hv/hver): the client learns which version its write-back
            # became, so flush completion can be ordered against
            # concurrent gradient traffic. Off = empty legacy reply.
            return msgpack.packb({"ver": ver})
        return b""

    def _clear(self, payload: bytes) -> bytes:
        self.holder.clear()
        return b""

    # --- live resharding (donor/target surface) --------------------------

    def _maybe_expire_reshard(self):
        """Donor self-healing: when the controller's lease on the
        in-flight migration state has expired (no reshard RPC renewed
        it), auto-thaw — discard capture state and unfreeze the moving
        slots, bouncing this replica back to the old epoch. Bounced
        writers' existing routing_stale retry path then settles at the
        CURRENT epoch transparently. Checked from the write guard, the
        health doc, and reshard_status, so both trafficked and idle
        donors recover. The fencing watermark stays: a zombie
        controller of the thawed migration is still refused."""
        rs = self._reshard
        if rs is None or not rs.lease_expired():
            return
        with self._reshard_lock:
            rs = self._reshard
            if rs is None or not rs.lease_expired():
                return
            self._reshard = None
        self._c_lease_expired.inc()
        if self._routing_epoch >= rs.epoch:
            # the migration's epoch already published to this replica:
            # the thaw is a self-finalize (exactly what reshard_finish
            # would have done) — moved rows stay as unreachable stale
            # copies
            _logger.warning(
                "reshard lease expired (%.1fs without a controller "
                "heartbeat): self-finalized migration %s — epoch %d "
                "already published, capture disarmed", rs.lease_sec,
                rs.mig_id, rs.epoch)
            return
        _logger.warning(
            "reshard lease expired (%.1fs without a controller "
            "heartbeat): auto-thawed migration %s pending epoch %d — "
            "capture discarded, %d slots unfrozen, serving the old "
            "epoch again. If the controller died MID-PUBLISH (some "
            "workers already on epoch %d), resume() from its journal "
            "promptly: old-epoch writers can now land on moved slots",
            rs.lease_sec, rs.mig_id, rs.epoch, int(rs.mask.sum()),
            rs.epoch)

    def _check_fence(self, fence, renew: bool = True):
        """Order a reshard RPC against the fencing watermark: tokens
        below it are refused (superseded controller), higher tokens
        advance it and DISCARD any state an older attempt left behind.
        ``fence=None`` (legacy unfenced controller) passes through.
        Returns the current state (possibly None) with its lease
        renewed."""
        from persia_tpu.reshard import FENCED_PREFIX

        from persia_tpu.rpc import RpcError

        if fence is None:
            rs = self._reshard
            if rs is not None and renew:
                rs.touch()
            return rs
        token = (int(fence[0]), int(fence[1]))
        with self._reshard_lock:
            if token < self._reshard_fence:
                raise RpcError(
                    f"{FENCED_PREFIX}{self._reshard_fence[0]}."
                    f"{self._reshard_fence[1]}")
            if token > self._reshard_fence:
                self._reshard_fence = token
                rs = self._reshard
                if rs is not None and rs.token is not None \
                        and rs.token < token:
                    # a newer attempt took over: the old attempt's
                    # capture/freeze state is dead weight — discard it
                    # (the new attempt re-begins from scratch)
                    self._reshard = None
                    _logger.warning(
                        "reshard state of superseded attempt %s/%s "
                        "discarded by newer token %s",
                        rs.mig_id, rs.token, token)
            rs = self._reshard
        if rs is not None and renew:
            rs.touch()
        return rs

    def _reshard_guard(self, signs: np.ndarray, meta: Optional[dict] = None):
        """Write-path gate: one None test when no migration runs. With
        a migration in flight, writes touching moving slots register
        for capture (and bounce once frozen). The negotiated ``re``
        meta rider short-circuits a frozen bounce before any hashing."""
        rs = self._reshard
        if rs is None:
            return None, None
        if rs.lease_expired():
            self._maybe_expire_reshard()
            rs = self._reshard
            if rs is None:
                return None, None
        if rs.frozen and meta is not None:
            ce = meta.get("re")
            if ce is not None and int(ce) < rs.epoch:
                from persia_tpu.routing import STALE_PREFIX
                from persia_tpu.rpc import RpcError

                raise RpcError(f"{STALE_PREFIX}{rs.epoch}")
        return rs, rs.enter_write(signs)

    def _reshard_begin(self, payload: bytes) -> bytes:
        """Arm capture for the moving slots, then snapshot their rows
        out of the backend's PSD stream (capture first: a write landing
        mid-snapshot is re-read at replay, so the copy can never miss
        it). The snapshot streams through a temp-file dump — every
        backend writes the same PSD record format (store.h's v2 stream
        included) — so donor RAM grows only by the MOVING rows, never
        by a whole-store blob. Returns the snapshot row count."""
        import tempfile

        from persia_tpu.ps.store import iter_psd_records, read_psd_header

        req = msgpack.unpackb(payload, raw=False)
        if faults._active:
            faults.fire("ps.reshard.begin", epoch=req.get("epoch"),
                        mig_id=req.get("mig_id"))
        self._maybe_expire_reshard()
        fence = req.get("fence")
        self._check_fence(fence, renew=False)
        rs = _ReshardState(req["slots"], req["num_slots"], req["epoch"],
                           mig_id=req.get("mig_id"), token=fence,
                           lease_sec=req.get("lease_sec"))
        with self._reshard_lock:
            cur = self._reshard
            if cur is not None:
                if (fence is not None and cur.token is not None
                        and tuple(cur.token) <= (int(fence[0]),
                                                 int(fence[1]))):
                    # idempotent re-begin: the same (or a newer) attempt
                    # re-arms from scratch — a retry after an ambiguous
                    # timeout, or a resumed controller whose
                    # fenced_finish raced this replica. The stale
                    # capture set is worthless (its rows re-snapshot
                    # below), so discarding it loses nothing.
                    _logger.warning(
                        "reshard_begin: re-arming over attempt %s/%s "
                        "with token %s", cur.mig_id, cur.token, fence)
                else:
                    raise RuntimeError(
                        "a slot migration is already in flight on this "
                        "replica")
            self._reshard = rs
            # barrier: writes already past the (then-absent) capture
            # gate must finish applying BEFORE the snapshot reads the
            # store, or an in-flight row lands in a shard the snapshot
            # already serialized — invisible to both copy and capture,
            # i.e. a lost update
            self._wgate.drain_prior()
        from persia_tpu.hashing import farmhash64_np

        pending: List = []

        def flush_pending():
            if not pending:
                return
            signs = np.array([r[0] for r in pending], np.uint64)
            slot = (farmhash64_np(signs)
                    % np.uint64(rs.num_slots)).astype(np.int64)
            keep = rs.mask[slot]
            rs.snapshot_rows.extend(
                r for r, k in zip(pending, keep) if k)
            pending.clear()

        fd, path = tempfile.mkstemp(prefix="persia_reshard_snap_")
        os.close(fd)
        try:
            self.holder.dump_file(path)
            with open(path, "rb") as fh:
                version, count = read_psd_header(fh, "<reshard-snapshot>")
                for rec in iter_psd_records(fh.read, version, count):
                    pending.append(rec)
                    if len(pending) >= 65536:
                        flush_pending()
                flush_pending()
        finally:
            os.unlink(path)
        _logger.info("reshard_begin: %d slots, %d snapshot rows, "
                     "epoch %d pending", int(rs.mask.sum()),
                     len(rs.snapshot_rows), rs.epoch)
        return msgpack.packb({"rows": len(rs.snapshot_rows)})

    def _reshard_extract(self, payload: bytes) -> bytes:
        from persia_tpu.reshard import pack_rows

        req = msgpack.unpackb(payload, raw=False)
        if faults._active:
            faults.fire("ps.reshard.extract",
                        max_rows=req.get("max_rows"))
        rs = self._check_fence(req.get("fence"))
        if rs is None:
            raise RuntimeError("no migration in flight")
        a = rs.extract_pos
        b = min(a + int(req.get("max_rows") or 65536),
                len(rs.snapshot_rows))
        rs.extract_pos = b
        chunk = pack_rows(rs.snapshot_rows[a:b])
        done = b >= len(rs.snapshot_rows)
        if done:
            rs.snapshot_rows = []  # freed; capture carries the rest
            rs.extract_pos = 0
        # scatter-gather framing: the packed chunk goes socketward
        # without the pack_arrays staging concat (wire bytes identical)
        return self._pack({"done": done},
                          [np.frombuffer(chunk, np.uint8)])

    def _reshard_install(self, payload: bytes) -> bytes:
        """Install a migrated row chunk on the target: batched per
        (dim, row width) through the vectorized set_entries path (a
        live target must not pay per-entry Python on millions of
        rows), versioned and committed to the inc-update log exactly
        like any other full-row write — a target that crashes after
        the migration reconstructs its migrated rows from the replay
        stream (see restore(routing=))."""
        from persia_tpu.reshard import unpack_row_runs

        meta, (blob,) = unpack_arrays(payload)
        if faults._active:
            faults.fire("ps.reshard.install", nbytes=len(blob),
                        mig_id=meta.get("mig_id"))
        # target-side fencing: an install from a superseded controller
        # (stale retry still in flight after a resume took over) must
        # not overwrite rows the new attempt already re-installed.
        # Repeated installs from the LIVE attempt are idempotent —
        # full-row set_entries writes.
        self._check_fence(meta.get("fence"), renew=False)
        # runs come out of the chunk as (signs, dim, record matrix) —
        # same-shape runs merge straight into one set_entries call
        # (one GIL-released batched write on the native holder), no
        # per-row unpack/stack staging
        by_shape: dict = {}
        for signs, dim, mat in unpack_row_runs(blob):
            by_shape.setdefault((dim, mat.shape[1]), []).append(
                (signs, mat))
        n = 0
        for (dim, _width), runs in by_shape.items():
            signs = (runs[0][0] if len(runs) == 1
                     else np.concatenate([s for s, _m in runs]))
            vecs = (runs[0][1] if len(runs) == 1
                    else np.concatenate([m for _s, m in runs]))
            self.holder.set_entries(signs, dim, vecs)
            self._bump_update_ver()
            if self.inc_dumper is not None:
                self.inc_dumper.commit(signs)
            n += len(signs)
        return msgpack.packb({"installed": n})

    def _reshard_drain(self, payload: bytes) -> bytes:
        """Ship the captured writes' CURRENT rows (a sign captured N
        times replays once, with its latest value + optimizer state).
        Frozen, this read is definitive — the cutover's final drain."""
        from persia_tpu.reshard import pack_rows

        req = (msgpack.unpackb(payload, raw=False) if payload else {})
        if faults._active:
            faults.fire("ps.reshard.drain",
                        frozen=bool(self._reshard
                                    and self._reshard.frozen))
        rs = self._check_fence(req.get("fence"))
        if rs is None:
            raise RuntimeError("no migration in flight")
        rows = []
        for sign in rs.drain_captured():
            entry = self.holder.get_entry(sign)
            if entry is not None:
                rows.append((sign, entry[0], entry[1]))
        chunk = pack_rows(rows)
        return self._pack({"rows": len(rows)},
                          [np.frombuffer(chunk, np.uint8)])

    def _reshard_freeze(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        if faults._active:
            faults.fire("ps.reshard.freeze", epoch=req.get("epoch"))
        rs = self._check_fence(req.get("fence"))
        if rs is None:
            raise RuntimeError("no migration in flight")
        if req.get("epoch") is not None:
            rs.epoch = int(req["epoch"])
        rs.freeze()
        _logger.info("reshard_freeze: moving slots write-frozen pending "
                     "epoch %d", rs.epoch)
        return b""

    def _reshard_finish(self, payload: bytes) -> bytes:
        """Disarm capture (cutover published + double-read window
        closed). Moved rows stay resident and simply age out of the
        LRU/arena like any cold row — they are unreachable under the
        new table, so correctness never depends on deleting them.
        Idempotent (a finished/never-armed replica answers
        ``was_active: False``) and fenced (a superseded controller's
        late finish must not disarm the newer attempt's capture)."""
        req = (msgpack.unpackb(payload, raw=False) if payload else {})
        if faults._active:
            faults.fire("ps.reshard.finish", mig_id=req.get("mig_id"))
        self._check_fence(req.get("fence"), renew=False)
        with self._reshard_lock:
            rs, self._reshard = self._reshard, None
        return msgpack.packb(
            {"was_active": rs is not None,
             "captured_total": rs.captured_total if rs else 0,
             "mig_id": rs.mig_id if rs else None})

    def _reshard_status(self, payload: bytes) -> bytes:
        req = (msgpack.unpackb(payload, raw=False) if payload else {})
        self._maybe_expire_reshard()
        # a fenced status doubles as the controller heartbeat (renews
        # the lease); unfenced status is a read-only observer probe
        rs = (self._check_fence(req["fence"]) if req.get("fence")
              else self._reshard)
        doc = {"active": rs is not None,
               "routing_epoch": self._routing_epoch,
               "fence": list(self._reshard_fence)}
        if rs is not None:
            with rs._lock:
                doc.update({
                    "frozen": rs.frozen,
                    "frozen_age_sec": (
                        round(time.monotonic() - rs.frozen_at, 3)
                        if rs.frozen else 0.0),
                    "pending_epoch": rs.epoch,
                    "mig_id": rs.mig_id,
                    "token": list(rs.token) if rs.token else None,
                    "lease_sec": rs.lease_sec,
                    "captured": len(rs.captured),
                    "captured_total": rs.captured_total,
                    "snapshot_rows_left": len(rs.snapshot_rows),
                })
        return msgpack.packb(doc)

    def _set_routing_epoch(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self._routing_epoch = int(req["epoch"])
        return b""

    def _set_status(self, status: str):
        with self._status_lock:
            self.status = status

    def _dump(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self._set_status("Dumping")

        def run():
            try:
                self.holder.dump_file(req["path"])
                self._set_status("Idle")
            except BaseException as e:  # recorded for status polling
                _logger.error("dump failed: %s", e)
                self._set_status(f"Failed: {e}")

        if req.get("blocking", True):
            run()
        else:
            threading.Thread(target=run, daemon=True).start()
        return b""

    def _load(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self._set_status("Loading")

        def run():
            try:
                self.holder.load_file(req["path"], clear=req.get("clear", True))
                self._set_status("Idle")
            except BaseException as e:
                _logger.error("load failed: %s", e)
                self._set_status(f"Failed: {e}")

        if req.get("blocking", True):
            run()
        else:
            threading.Thread(target=run, daemon=True).start()
        return b""

    def restore(self, checkpoint_path: Optional[str] = None,
                replay_inc_dir: Optional[str] = None,
                replica_index: Optional[int] = None,
                routing=None) -> int:
        """Crash-recovery boot restore: load this replica's last
        checkpoint shard, then replay any incremental-update packets
        newer than it (the train-side dumper's ``inc_*`` directories) on
        top — together they reconstruct every durably-recorded row. The
        status machine rides along, so ``/healthz?ready=1`` answers 503
        until the restore completes (the supervisor and k8s probes must
        not route to a replica mid-restore). Returns the number of
        replayed incremental entries."""
        self._set_status("Loading")
        replayed = 0
        try:
            if checkpoint_path and routing is not None:
                # shard-layout-change recovery: the per-replica file
                # was sharded by the OLD table, so load only the rows
                # the NEW table routes here — rows this replica no
                # longer owns would shadow the live owner's state at
                # the next checkpoint merge. (Rows it gained from
                # OTHER old shards come back through the routing-
                # filtered inc replay below; a full reconstruction
                # across layouts restores the whole directory via
                # checkpoint.load_sharded instead.)
                from persia_tpu.checkpoint import iter_psd_entries

                kept = 0
                batch: List = []

                def flush_batch():
                    nonlocal kept
                    if not batch:
                        return
                    owners = routing.replica_of(np.array(
                        [b[0] for b in batch], np.uint64))
                    for (sign, dim, vec), o in zip(batch, owners):
                        if int(o) == replica_index:
                            self.holder.set_entry(sign, dim, vec)
                            kept += 1
                    batch.clear()

                for rec in iter_psd_entries(checkpoint_path):
                    batch.append(rec)
                    if len(batch) >= 65536:
                        flush_batch()
                flush_batch()
                _logger.info(
                    "restored checkpoint %s (%d rows kept under the "
                    "live routing table)", checkpoint_path, kept)
            elif checkpoint_path:
                self.holder.load_file(checkpoint_path)
                _logger.info("restored checkpoint %s (%d entries)",
                             checkpoint_path, len(self.holder))
            if replay_inc_dir:
                from persia_tpu.inc_update import IncrementalUpdateLoader

                replayed = IncrementalUpdateLoader(
                    self.holder, replay_inc_dir,
                    replica_index=replica_index,
                    routing=routing).scan_once()
                _logger.info("replayed %d incremental entries from %s",
                             replayed, replay_inc_dir)
            self._set_status("Idle")
        except BaseException as e:
            _logger.error("restore failed: %s", e)
            self._set_status(f"Failed: {e}")
            raise
        return replayed

    def _status(self, payload: bytes) -> bytes:
        with self._status_lock:
            return msgpack.packb({"status": self.status})

    def _ready(self, payload: bytes) -> bytes:
        ready = (
            getattr(self.holder, "optimizer", True) is not None
            and self.status == "Idle"
        )
        return msgpack.packb({"ready": bool(ready)})


class PsClient:
    """RPC twin of the in-process holder interface.

    ``enable_tags`` (default) negotiates tagged framing per connection:
    lookups/updates can then be issued as futures
    (:meth:`lookup_future` / :meth:`update_gradients_future`) that
    multiplex on one socket, and a dispatch-pool server completes them
    out of order. Legacy servers (e.g. the C++ ``ps_server``) negotiate
    down transparently; the future methods then degrade to synchronous
    calls.

    Every RPC passes through a per-replica **circuit breaker** (default
    on; ``PERSIA_PS_CIRCUIT_BREAKER=0`` or ``circuit_breaker=False``
    disables): after ``CB_THRESHOLD`` consecutive calls that exhausted
    the transport retry ladder, the breaker opens and calls fail fast
    with :class:`~persia_tpu.rpc.RpcCircuitOpen` — no wire traffic, no
    per-call backoff ladder against a dead replica — while a background
    TCP probe watches the address; the first accept arms a single
    half-open trial call whose success re-closes the breaker. The
    worker's re-arm/refresh recovery path sees ``RpcCircuitOpen`` as an
    ordinary ``ConnectionError``. ``deadline`` (seconds) arms per-call
    deadline propagation (negotiated; see rpc.py)."""

    CB_THRESHOLD = 3
    CB_COOLDOWN = 1.0

    # PERSIA_PS_WIRE_CODEC / wire_codec= values -> (fp16 lookups,
    # int8 updates). Opt-in: unset/off keeps the fp32 wire
    # byte-identical to the legacy protocol.
    _WIRE_CODECS = {
        "": (False, False), "0": (False, False), "off": (False, False),
        "fp32": (False, False),
        "fp16": (True, False),
        "int8": (False, True),
        "fp16+int8": (True, True), "full": (True, True),
    }

    @classmethod
    def parse_wire_codec(cls, value) -> tuple:
        """Strict policy parse -> (fp16 lookups, int8 updates). A typo'd
        PERSIA_PS_WIRE_CODEC must fail LOUDLY everywhere (a silent
        codec-off is exactly the silent downgrade the native-backend
        lint exists to prevent)."""
        try:
            return cls._WIRE_CODECS[str(value).lower()]
        except KeyError:
            raise ValueError(
                f"unknown wire codec {value!r} (expected one of "
                f"{sorted(cls._WIRE_CODECS)})") from None

    def __init__(self, addr: str, enable_tags: bool = True,
                 circuit_breaker=None, deadline: Optional[float] = None,
                 wire_codec: Optional[str] = None,
                 hotness: Optional[bool] = None,
                 routing_wire: Optional[bool] = None):
        self.addr = addr
        # routing-epoch rider (None -> PERSIA_ROUTING_WIRE env): armed,
        # the connection probes __routing__ at dial and every lookup/
        # update stamps this client's routing epoch ("re" meta) so a
        # mid-reshard server fast-rejects stale-epoch writes. Off (the
        # default) sends no probe and no rider — byte-identical wire;
        # legacy servers refuse the probe and negotiate down.
        if routing_wire is None:
            routing_wire = knobs.get("PERSIA_ROUTING_WIRE")
        self.routing_wire = bool(routing_wire)
        self.routing_epoch: Optional[int] = None
        # workload telemetry (None -> PERSIA_HOTNESS env): armed, every
        # lookup asks for the replica's update version ("hv" request
        # meta) and every update echoes the last seen one back
        # ("hver"), giving the server its gradient-staleness histogram.
        # Off (the default), neither key exists and the wire stays
        # byte-identical to the legacy protocol. A legacy/unarmed
        # server simply never answers "hver" — negotiate-down for free.
        if hotness is None:
            hotness = knobs.get("PERSIA_HOTNESS")
        self.telemetry = bool(hotness)
        self._last_hver: Optional[int] = None
        # last update version a versioned set_entries write-back became
        # (None until the first armed write-back answers)
        self.last_writeback_ver: Optional[int] = None
        # wire codec policy (None -> PERSIA_PS_WIRE_CODEC env): "fp16"
        # ships lookup responses as fp16 rows, "fp16+int8" additionally
        # ships update gradients as int8 + per-row scales with the fp32
        # error-feedback residual held client-side. Negotiated per
        # connection (rpc.py __codec__ probe): a legacy server
        # negotiates down to the fp32 wire transparently, and with the
        # codec off the wire is byte-identical to the legacy protocol.
        if wire_codec is None:
            wire_codec = knobs.get("PERSIA_PS_WIRE_CODEC")
        self.wire_fp16, self.wire_int8 = self.parse_wire_codec(wire_codec)
        self.client = RpcClient(addr, enable_tags=enable_tags,
                                deadline=deadline,
                                enable_codec=self.wire_fp16
                                or self.wire_int8,
                                enable_routing=self.routing_wire)
        if self.wire_int8:
            from persia_tpu.worker.middleware import GradErrorFeedback

            self._ef = GradErrorFeedback()
        else:
            self._ef = None
        self._pack = pack_arrays_sg
        if circuit_breaker is None:
            circuit_breaker = (
                knobs.get("PERSIA_PS_CIRCUIT_BREAKER"))
        if circuit_breaker is True:
            circuit_breaker = CircuitBreaker(
                threshold=self.CB_THRESHOLD, cooldown=self.CB_COOLDOWN,
                probe=tcp_probe(addr))
        elif circuit_breaker is False:
            circuit_breaker = None
        self.breaker: Optional[CircuitBreaker] = circuit_breaker

    def _check_open(self):
        br = self.breaker
        if br is not None and not br.allow():
            raise RpcCircuitOpen(
                f"{self.addr}: circuit open (failing fast after "
                f"{br.threshold} consecutive transport failures)")

    def _settle(self, fn):
        """Record one RPC's outcome on the breaker: transport-level
        loss (incl. our typed subclasses) trips it; an application
        error means the replica ANSWERED — the transport is healthy, so
        it counts as breaker success (critically, this releases the
        half-open trial slot: a restarted-blank replica whose trial
        call errs at the application layer must close the breaker, not
        wedge it open forever)."""
        br = self.breaker
        try:
            out = fn()
        except (ConnectionError, OSError):
            if br is not None:
                br.record_failure()
            raise
        except BaseException:
            if br is not None:
                br.record_success()
            raise
        if br is not None:
            br.record_success()
        return out

    def _guarded(self, fn):
        """Run one blocking RPC under the breaker (fail fast when open,
        then settle). The future paths split the two halves: issue under
        :meth:`_check_open`, settle at resolve time."""
        self._check_open()
        return self._settle(fn)

    def configure(self, init_method, init_params, admit_probability=1.0,
                  weight_bound=10.0, enable_weight_bound=True):
        self._guarded(lambda: self.client.call_msg(
            "configure", init_method=init_method, init_params=init_params,
            admit_probability=admit_probability, weight_bound=weight_bound,
            enable_weight_bound=enable_weight_bound,
        ))

    def register_optimizer(self, config: dict, feature_index_prefix_bit=0):
        self._guarded(lambda: self.client.call_msg(
            "register_optimizer", config=config,
            feature_index_prefix_bit=feature_index_prefix_bit,
        ))

    def _lookup_meta(self, dim: int, training: bool) -> dict:
        meta = {"dim": int(dim), "training": bool(training)}
        if self.wire_fp16 and self.client.codec_active():
            meta["resp"] = "fp16"
        if self.telemetry:
            meta["hv"] = 1
        if (self.routing_wire and self.routing_epoch is not None
                and self.client.routing_active()):
            meta["re"] = int(self.routing_epoch)
        return meta

    def _note_hver(self, meta: dict):
        """Remember the update version a lookup response reported (a
        plain attribute store — atomic under the GIL; concurrent
        lookups may interleave, and any recently-seen version is an
        equally valid staleness anchor)."""
        hv = meta.get("hver")
        if hv is not None:
            self._last_hver = int(hv)

    @staticmethod
    def _decode_rows(meta: dict, out: np.ndarray, n: int,
                     dim: int) -> np.ndarray:
        """Decode a lookup response by what it SAYS it is (response
        meta): a legacy server ignores the fp16 request and answers
        fp32, so the decode must key on the reply, not the ask."""
        if meta.get("codec") == "fp16":
            from persia_tpu import wire_codec

            out = wire_codec.decode_fp16_rows(out)
        return out.reshape(n, dim)

    def _update_meta(self, dim: int) -> dict:
        meta = {"dim": int(dim)}
        if self.telemetry and self._last_hver is not None:
            meta["hver"] = self._last_hver
        if (self.routing_wire and self.routing_epoch is not None
                and self.client.routing_active()):
            meta["re"] = int(self.routing_epoch)
        return meta

    def _update_payload(self, signs: np.ndarray, grads: np.ndarray,
                        dim: int):
        signs = np.ascontiguousarray(signs, np.uint64)
        grads = np.ascontiguousarray(grads, np.float32)
        if self.wire_int8 and self.client.codec_active():
            from persia_tpu import wire_codec

            # error-feedback int8: compensate this shipment with the
            # signs' stored residuals, quantize per row, store the new
            # residuals for the next shipment (grads copied — callers'
            # buffers must not grow feedback noise)
            g = grads.copy()
            self._ef.apply(signs, g, dim)
            q, scales, residual = wire_codec.quantize_int8_rows(g)
            self._ef.store(signs, residual, dim)
            return self._pack({**self._update_meta(dim), "codec": "int8"},
                              [signs, q, scales])
        return self._pack(self._update_meta(dim), [signs, grads])

    def lookup(self, signs: np.ndarray, dim: int, training: bool) -> np.ndarray:
        self._check_open()
        payload = self._pack(self._lookup_meta(dim, training),
                                 [np.ascontiguousarray(signs, np.uint64)])
        meta, (out,) = unpack_arrays(
            self._settle(lambda: self.client.call("lookup", payload)))
        self._note_hver(meta)
        return self._decode_rows(meta, out, len(signs), dim)

    def lookup_future(self, signs: np.ndarray, dim: int, training: bool):
        """Issue the lookup without waiting; returns a zero-arg resolver
        producing the (n, dim) matrix. Multiple in-flight lookups
        multiplex on this thread's one connection (tag-matched), so a
        slow (shard, dim) group no longer blocks the fast ones. The
        breaker gates the ISSUE (fail fast when open) and settles on
        the resolver's outcome."""
        self._check_open()
        n = len(signs)
        payload = self._pack(self._lookup_meta(dim, training),
                                 [np.ascontiguousarray(signs, np.uint64)])
        fut = self._settle(
            lambda: self.client.call_future("lookup", payload))

        def resolve() -> np.ndarray:
            meta, (out,) = unpack_arrays(self._settle(fut.result))
            self._note_hver(meta)
            return self._decode_rows(meta, out, n, dim)

        return resolve

    def update_gradients(self, signs: np.ndarray, grads: np.ndarray, dim: int):
        self._check_open()
        payload = self._update_payload(signs, grads, dim)
        # non-idempotent: dedup id makes the retry at-most-once server-side
        # (blocking path keeps the client's full retry-with-backoff)
        self._settle(lambda: self.client.call("update_gradients", payload,
                                              dedup=True))

    def update_gradients_future(self, signs: np.ndarray, grads: np.ndarray,
                                dim: int):
        """Issue the gradient push without waiting; returns a zero-arg
        resolver that raises on failure. Already-aggregated groups ship
        while later ones are still aggregating (worker streaming)."""
        self._check_open()
        payload = self._update_payload(signs, grads, dim)
        # non-idempotent: dedup id makes the retry at-most-once server-side
        fut = self._settle(lambda: self.client.call_future(
            "update_gradients", payload, dedup=True))

        def resolve():
            self._settle(fut.result)

        return resolve

    def health(self) -> dict:
        """The PS replica's health document over RPC (resident bytes,
        row_dtype, served counts) — what the bench and capacity tooling
        read without scraping the HTTP sidecar."""
        return msgpack.unpackb(
            self._guarded(lambda: self.client.call("health")), raw=False)

    def hotness(self) -> dict:
        """The replica's workload-hotness snapshot (persia_tpu.hotness
        format; the disabled marker when sketches are unarmed)."""
        return msgpack.unpackb(
            self._guarded(lambda: self.client.call("hotness")),
            raw=False)

    def wire_stats(self) -> dict:
        """Cumulative payload bytes this client sent/received (rpc.py
        counters) — the bytes-on-wire accounting
        ``tests/test_precision.py`` holds the codec to."""
        return self.client.wire_stats()

    def __len__(self) -> int:
        return msgpack.unpackb(
            self._guarded(lambda: self.client.call("len")),
            raw=False)["len"]

    def get_entry(self, sign: int):
        payload = msgpack.packb({"sign": int(sign)}, use_bin_type=True)
        meta, arrays = unpack_arrays(
            self._guarded(lambda: self.client.call("get_entry", payload)))
        if not meta["found"]:
            return None
        return meta["dim"], arrays[0]

    def set_entry(self, sign: int, dim: int, vec: np.ndarray):
        self._guarded(lambda: self.client.call("set_entry", pack_arrays(
            {"sign": int(sign), "dim": int(dim)},
            [np.ascontiguousarray(vec, np.float32)],
        )))

    def get_entries(self, signs: np.ndarray, width: int):
        payload = self._pack({"width": int(width)}, [
            np.ascontiguousarray(signs, np.uint64)])
        _, (found, vecs) = unpack_arrays(
            self._guarded(lambda: self.client.call("get_entries", payload)))
        return (found.astype(bool),
                vecs.reshape(len(signs), width).astype(np.float32))

    def set_entries(self, signs: np.ndarray, dim: int, vecs: np.ndarray):
        meta = {"dim": int(dim)}
        if self.telemetry:
            # versioned write-back (tier-ladder coherence): ask the
            # replica which update version this write became; off, the
            # request and the empty reply are byte-identical to legacy
            meta["wv"] = 1
        resp = self._guarded(lambda: self.client.call(
            "set_entries", self._pack(meta, [
                np.ascontiguousarray(signs, np.uint64),
                np.ascontiguousarray(vecs, np.float32),
            ]), dedup=True))
        if meta.get("wv") and resp:
            ver = msgpack.unpackb(resp, raw=False).get("ver")
            if ver is not None:
                # GIL-atomic store like _note_hver; any recent version
                # is a valid ordering anchor
                self.last_writeback_ver = int(ver)

    def clear(self):
        self._guarded(lambda: self.client.call("clear"))

    # --- live-resharding surface (persia_tpu.reshard drives these) -------
    #
    # Every method takes an optional ``fence`` token ((epoch, attempt),
    # see reshard.py) the server orders against its watermark, and rides
    # the PERSIA_RESHARD_RPC_TIMEOUT_SEC deadline once
    # :meth:`enable_reshard_deadline` armed the connection — so a
    # wedged replica sheds the expired call instead of hanging the
    # migration. ``fence=None`` keeps the legacy unfenced protocol.

    def enable_reshard_deadline(self):
        """Arm PERSIA_RESHARD_RPC_TIMEOUT_SEC on this client: future
        reshard RPCs carry the negotiated ``__deadline__`` envelope
        slot. The calling thread's pooled connection is dropped so the
        next call re-dials WITH the probe; called by the controller at
        migration start, so fleets that never reshard never send it —
        their wire stays byte-identical."""
        timeout = float(knobs.get("PERSIA_RESHARD_RPC_TIMEOUT_SEC"))
        if timeout <= 0:
            return
        self._reshard_rpc_deadline = timeout
        if not self.client.enable_deadline:
            self.client.enable_deadline = True
            self.client.renegotiate()

    def _reshard_call_kw(self) -> dict:
        dl = getattr(self, "_reshard_rpc_deadline", None)
        return {"deadline": dl} if dl else {}

    def reshard_begin(self, slots, num_slots: int, epoch: int,
                      fence=None, mig_id: Optional[str] = None,
                      lease_sec: Optional[float] = None) -> int:
        """Donor: arm write capture for ``slots`` and snapshot their
        rows; returns the snapshot row count. Fenced re-begins with the
        same (or a newer) token re-arm idempotently — the retry path of
        a resumed controller."""
        payload = {"slots": [int(s) for s in slots],
                   "num_slots": int(num_slots), "epoch": int(epoch)}
        if fence is not None:
            payload.update(fence=[int(fence[0]), int(fence[1])],
                           mig_id=mig_id)
        if lease_sec is not None:
            payload["lease_sec"] = float(lease_sec)
        rep = msgpack.unpackb(self._guarded(
            lambda: self.client.call(
                "reshard_begin",
                msgpack.packb(payload, use_bin_type=True),
                **self._reshard_call_kw())), raw=False)
        return int(rep["rows"])

    def reshard_extract(self, max_rows: int, fence=None):
        """Donor: next snapshot chunk. Returns (row_blob, done)."""
        req = {"max_rows": int(max_rows)}
        if fence is not None:
            req["fence"] = [int(fence[0]), int(fence[1])]
        meta, (blob,) = unpack_arrays(self._guarded(
            lambda: self.client.call(
                "reshard_extract",
                msgpack.packb(req, use_bin_type=True),
                **self._reshard_call_kw())))
        return bytes(blob), bool(meta["done"])

    def reshard_install(self, row_blob: bytes, fence=None,
                        mig_id: Optional[str] = None) -> int:
        """Target: install a row chunk (value + optimizer state).
        Idempotent by construction (full-row writes) and fenced, so
        retry-after-timeout and resume-re-copy are both safe."""
        meta = {}
        if fence is not None:
            meta = {"fence": [int(fence[0]), int(fence[1])],
                    "mig_id": mig_id}
        rep = msgpack.unpackb(self._guarded(
            lambda: self.client.call("reshard_install", pack_arrays(
                meta, [np.frombuffer(row_blob, np.uint8)]), dedup=True,
                **self._reshard_call_kw())),
            raw=False)
        return int(rep["installed"])

    def reshard_drain(self, fence=None) -> bytes:
        """Donor: current rows of the captured writes (clears the
        capture set)."""
        payload = (msgpack.packb(
            {"fence": [int(fence[0]), int(fence[1])]},
            use_bin_type=True) if fence is not None else b"")
        _meta, (blob,) = unpack_arrays(self._guarded(
            lambda: self.client.call("reshard_drain", payload,
                                     **self._reshard_call_kw())))
        return bytes(blob)

    def reshard_freeze(self, epoch: Optional[int] = None, fence=None,
                       mig_id: Optional[str] = None):
        """Donor: stop admitting writes for the moving slots (bounces
        carry ``epoch`` as the demanded successor epoch). Idempotent:
        an already-frozen state re-waits its (empty) barrier."""
        payload = {"epoch": epoch}
        if fence is not None:
            payload.update(fence=[int(fence[0]), int(fence[1])],
                           mig_id=mig_id)
        self._guarded(lambda: self.client.call(
            "reshard_freeze", msgpack.packb(payload, use_bin_type=True),
            **self._reshard_call_kw()))

    def reshard_finish(self, fence=None,
                       mig_id: Optional[str] = None) -> dict:
        payload = b""
        if fence is not None:
            payload = msgpack.packb(
                {"fence": [int(fence[0]), int(fence[1])],
                 "mig_id": mig_id}, use_bin_type=True)
        return msgpack.unpackb(self._guarded(
            lambda: self.client.call("reshard_finish", payload,
                                     **self._reshard_call_kw())),
            raw=False)

    def reshard_status(self, fence=None) -> dict:
        """Migration state probe; with ``fence`` it doubles as the
        controller's lease heartbeat."""
        payload = b""
        if fence is not None:
            payload = msgpack.packb(
                {"fence": [int(fence[0]), int(fence[1])]},
                use_bin_type=True)
        return msgpack.unpackb(self._guarded(
            lambda: self.client.call("reshard_status", payload,
                                     **self._reshard_call_kw())),
            raw=False)

    def set_routing_epoch(self, epoch: int):
        """Record the published routing epoch on the replica (rides
        health docs and the __routing__ ack) and stamp it on this
        client's future rider-armed requests."""
        self.routing_epoch = int(epoch)
        self._guarded(lambda: self.client.call_msg(
            "set_routing_epoch", epoch=int(epoch)))

    def dump_file(self, path: str, blocking: bool = True):
        self._guarded(lambda: self.client.call_msg(
            "dump", path=path, blocking=blocking))

    def load_file(self, path: str, clear: bool = True, blocking: bool = True):
        self._guarded(lambda: self.client.call_msg(
            "load", path=path, clear=clear, blocking=blocking))

    def model_manager_status(self) -> str:
        return msgpack.unpackb(
            self._guarded(lambda: self.client.call("status")),
            raw=False)["status"]

    def ready_for_serving(self) -> bool:
        return msgpack.unpackb(
            self._guarded(lambda: self.client.call("ready_for_serving")),
            raw=False)["ready"]

    def shutdown(self):
        self.client.shutdown_server()


def main():
    from persia_tpu.config import GlobalConfig
    from persia_tpu.ps.native import make_holder

    p = argparse.ArgumentParser()
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--replica-index", type=int,
                   default=int(os.environ.get("REPLICA_INDEX", 0)))
    p.add_argument("--replica-size", type=int,
                   default=int(os.environ.get("REPLICA_SIZE", 1)))
    p.add_argument("--coordinator",
                   default=knobs.get_raw("PERSIA_COORDINATOR_ADDR"))
    p.add_argument("--global-config", default=None)
    p.add_argument("--initial-checkpoint", default=None)
    p.add_argument("--replay-inc-dir", default=None,
                   help="after --initial-checkpoint, replay incremental "
                        "update packets (inc_update dumper output) on top "
                        "of the restored store — the supervisor's crash "
                        "recovery path")
    p.add_argument("--addr-file", default=None,
                   help="write the bound address here after listen (with "
                        "--port 0: race-free port handoff to a parent)")
    p.add_argument("--row-dtype",
                   default=knobs.get("PERSIA_PS_ROW_DTYPE"),
                   choices=["fp32", "fp16", "bf16"],
                   help="storage precision of the embedding slice of "
                        "every row (optimizer state stays fp32); "
                        "overrides the global config's "
                        "parameter_server.row_dtype. Served by the "
                        "native arena store when built (an old pre-"
                        "arena .so negotiates down to the Python arena "
                        "holder loudly; PERSIA_PS_BACKEND pins one)")
    p.add_argument("--spill-dir",
                   default=knobs.get("PERSIA_TIER_SPILL_DIR"),
                   help="arm the disk spill tier: budget evictions "
                        "demote rows to spill packets under "
                        "<dir>/r<replica-index> (PersiaPath — local or "
                        "hdfs://) instead of dropping them; lookups "
                        "fault them back transparently. Works on every "
                        "backend (the native store drains evictions to "
                        "the shared Python SpillStore). Overrides "
                        "parameter_server.spill_dir")
    p.add_argument("--spill-bytes", type=int,
                   default=knobs.get("PERSIA_TIER_SPILL_BYTES"),
                   help="disk budget for the spill tier (0 = "
                        "unbounded); oldest packets are dropped whole "
                        "on overflow")
    from persia_tpu import obs_http

    obs_http.add_http_args(p)
    p.add_argument("--concurrent-streams", type=int,
                   default=knobs.get("PERSIA_PS_CONCURRENT_STREAMS"),
                   help="per-connection dispatch pool depth (1 = the "
                        "legacy strictly-serial per-connection loop); "
                        "shard-parallel execution is controlled "
                        "separately by PERSIA_PS_SHARD_PARALLEL=0/1")
    args = p.parse_args()
    from persia_tpu.tracing import set_service_name, start_deadlock_detection

    start_deadlock_detection()
    set_service_name(f"ps{args.replica_index}")
    # Freeze boot state and make full collections rare. The per-entry
    # holder keeps millions of gc-tracked objects (per-entry tuples,
    # dict nodes); CPython's default gen2 cadence then walks the ENTIRE
    # store every few seconds of traffic — multi-hundred-ms request
    # stalls that scale with resident rows. The arena backends keep
    # rows in a handful of GC-invisible slab buffers and do not need
    # it, but every replica has always run with it.
    # (aliased import: `gc` is this function's GlobalConfig below)
    import gc as _gcmod

    _gcmod.collect()
    _gcmod.freeze()
    _gcmod.set_threshold(50_000, 25, 100)

    gc = GlobalConfig.load(args.global_config) if args.global_config else GlobalConfig()
    # replicas share one spill_dir config; each keeps its packets in
    # its own subdirectory (the inc_update packet-name convention)
    spill_dir = args.spill_dir or gc.parameter_server.spill_dir or None
    if spill_dir:
        spill_dir = os.path.join(spill_dir, f"r{args.replica_index}")
    holder = make_holder(gc.parameter_server.capacity,
                         gc.parameter_server.num_hashmap_internal_shards,
                         row_dtype=args.row_dtype
                         or gc.parameter_server.row_dtype,
                         capacity_bytes=gc.parameter_server.capacity_bytes
                         or None,
                         spill_dir=spill_dir,
                         spill_bytes=args.spill_bytes
                         or gc.parameter_server.spill_bytes or None)
    inc_dumper = None
    inc_loader = None
    if gc.parameter_server.enable_incremental_update:
        from persia_tpu.config import JobType
        from persia_tpu.inc_update import (
            IncrementalUpdateDumper,
            IncrementalUpdateLoader,
        )

        if gc.common.job_type == JobType.INFER:
            inc_loader = IncrementalUpdateLoader(
                holder, gc.parameter_server.incremental_dir)
            inc_loader.start()
        else:
            inc_dumper = IncrementalUpdateDumper(
                holder, gc.parameter_server.incremental_dir,
                buffer_size=gc.parameter_server.incremental_buffer_size,
                replica_index=args.replica_index,
            )
    service = PsService(
        holder, args.host, args.port, inc_dumper=inc_dumper,
        inc_loader=inc_loader,
        concurrent_streams=args.concurrent_streams,
        http_port=obs_http.port_from_args(args))
    if args.initial_checkpoint or args.replay_inc_dir:
        # restore BEFORE registering with the coordinator, so workers
        # never route to a half-restored replica; the sidecar is already
        # up and reports ready=false (503 on /healthz?ready=1) meanwhile
        service.restore(args.initial_checkpoint, args.replay_inc_dir,
                        replica_index=args.replica_index)
    _logger.info("parameter server %d/%d listening on %s (sidecar %s)",
                 args.replica_index, args.replica_size, service.addr,
                 service.http.addr if service.http else "off")
    if args.addr_file:
        from persia_tpu.utils import write_addr_file

        write_addr_file(service.addr, args.addr_file)
    obs_http.write_addr_file_from_args(service.http, args)
    if args.coordinator:
        # the sidecar address rides the registration so the fleet
        # monitor can discover every scrape target from the coordinator
        CoordinatorClient(args.coordinator).register(
            ROLE_PS, args.replica_index, service.addr,
            http_addr=service.http.addr if service.http else None)
    service.server.serve_forever()


if __name__ == "__main__":
    main()
