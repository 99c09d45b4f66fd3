"""The embedding worker: middleware state + PS fan-out.

Plays the role of the reference's EmbeddingWorkerInner
(embedding_worker_service/mod.rs:631-1129): it owns

- ``forward_id_buffer`` — batches sent by data-loaders awaiting lookup,
  keyed by ref_id (mod.rs:656-701)
- ``post_forward_buffer`` — looked-up batches awaiting gradients
  (mod.rs:1060-1067)
- a ``staleness`` counter (incremented at lookup, decremented when the
  gradients return, mod.rs:75-80)
- fan-out to the parameter-server replicas through any client exposing the
  holder interface (in-process holders here; RPC clients in
  persia_tpu.service wire the same calls over TCP)

Expiry of stale pending batches after ``buffered_data_expired_sec``
mirrors mod.rs:991-1029.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from persia_tpu import tracing
from persia_tpu.config import EmbeddingSchema
from persia_tpu.data.batch import IDTypeFeature
from persia_tpu.logger import get_default_logger
from persia_tpu.worker import middleware as mw

_logger = get_default_logger(__name__)


class ForwardBufferFull(RuntimeError):
    """Backpressure signal to data-loaders (reference mod.rs:1519-1521)."""


_WORKER_SEQ = [0]
_WORKER_SEQ_LOCK = threading.Lock()


class EmbeddingWorker:
    """Stateless-ish middleware between trainers and parameter servers."""

    # multiplex a replica's (shard,dim) group lookups on one connection
    # only when there are at least this many — below it, a fan-out
    # thread per group (server answers inline on the reader thread) is
    # cheaper than the server-side dispatch pool
    MUX_MIN_GROUPS = 3
    # in-flight bound per multiplexed connection: keeps the replica's
    # concurrent handler count comparable to the thread-per-group plane
    # (unbounded fan-in made insert-heavy lookups CONTEND on the
    # store's shard mutexes and the allocator, measured slower)
    MUX_WINDOW = 2

    def __init__(
        self,
        schema: EmbeddingSchema,
        ps_clients: Sequence,
        forward_buffer_size: int = 1000,
        buffered_data_expired_sec: int = 1800,
        enable_monitor: bool = False,
        ps_resolver=None,
        streaming: Optional[bool] = None,
        routing=None,
        routing_fetch=None,
    ):
        self.schema = schema
        self.ps_clients = list(ps_clients)
        # Re-resolve the PS replica list after connection-level failures
        # (reference: the worker refreshes its PS client list on RpcError,
        # embedding_worker_service/mod.rs:1320-1333). A PS that restarts
        # on a NEW port (local mode, no k8s service DNS) re-registers with
        # the coordinator; the resolver returns the fresh client list.
        self._ps_resolver = ps_resolver
        self._ps_lock = threading.Lock()
        # serializes recovery passes: two RPC threads failing concurrently
        # must not both re-arm a restarted PS (the second register would
        # wipe optimizer state the first retry already built on)
        self._rearm_lock = threading.Lock()
        self.replica_size = len(self.ps_clients)
        if self.replica_size == 0:
            raise ValueError("EmbeddingWorker needs at least one PS client")
        # Slot-table routing (persia_tpu.routing): every shard decision
        # reads ONE immutable table through this atomic-swap cell. The
        # launch default is the uniform table — bit-exact legacy
        # farmhash % R routing, native fast path intact. The reshard
        # controller (or a coordinator watcher) installs successor
        # epochs via apply_routing; `routing_fetch` (optional callable
        # returning the latest published table) lets the stale-retry
        # path pull the new epoch itself when nobody pushes it.
        from persia_tpu.routing import RoutingHolder, RoutingTable

        if routing is None:
            routing = RoutingTable.uniform(self.replica_size)
        elif routing.num_replicas > self.replica_size:
            raise ValueError(
                f"routing table references {routing.num_replicas} "
                f"replicas but only {self.replica_size} PS clients given")
        self._routing = RoutingHolder(routing)
        self._routing_fetch = routing_fetch
        self.forward_buffer_size = forward_buffer_size
        self.buffered_data_expired_sec = buffered_data_expired_sec
        # Concurrent fan-out to the PS replicas (the reference joins all
        # per-shard RPC futures, mod.rs:448-484): with N remote replicas
        # over DCN a serial loop costs N x the lookup latency. Each RPC
        # client pools one connection per calling thread, so concurrent
        # calls to the same replica are safe. In-process holders on a
        # single-core host gain nothing from threads (pure GIL/context
        # switch overhead), so fan out only when a client is remote
        # (has a network address) or real parallelism exists.
        import os

        remote = any(hasattr(c, "addr") for c in self.ps_clients)
        self._fanout = (
            ThreadPoolExecutor(
                max_workers=min(2 * self.replica_size, 32),
                thread_name_prefix="ps-fanout",
            )
            if self.replica_size > 1 and (remote or (os.cpu_count() or 1) > 1)
            else None
        )
        self._lock = threading.Lock()
        self._next_ref_id = 1
        # ref_id -> (feats, enter_time)
        self._forward_id_buffer: Dict[int, Tuple[list, float]] = {}
        # ref_id -> (feats, shard groups from the forward split, enter_time)
        self._post_forward_buffer: Dict[int, tuple] = {}
        self.staleness = 0
        # distinct-id cardinality estimation (reference monitor.rs)
        from persia_tpu.worker.monitor import DistinctIdMonitor

        self.monitor = DistinctIdMonitor() if enable_monitor else None
        from persia_tpu.metrics import default_registry

        # Streaming data plane (default on): per-(shard,dim) lookup
        # results scatter into the output as each RPC completes, and
        # aggregated gradient groups ship while later features are still
        # aggregating. streaming=False restores the gather-then-scatter /
        # aggregate-then-ship serialized plane (the bench baseline).
        if streaming is None:
            from persia_tpu import knobs

            streaming = knobs.get("PERSIA_WORKER_STREAMING")
        self.streaming = bool(streaming)
        reg = default_registry()
        # each worker instance gets its own labeled series so two
        # workers in one process (e.g. the bench's A/B stacks) don't
        # blend their stage timings; the metric NAMES stay the
        # reference's (grafana dashboard contract)
        with _WORKER_SEQ_LOCK:
            _WORKER_SEQ[0] += 1
            labels = {"worker": str(_WORKER_SEQ[0])}
        self._t_preprocess = reg.histogram(
            "lookup_preprocess_time_cost_sec", labels)
        self._t_rpc = reg.histogram("lookup_rpc_time_cost_sec", labels)
        self._t_postprocess = reg.histogram(
            "lookup_postprocess_time_cost_sec", labels)
        self._t_aggregate = reg.histogram(
            "update_aggregate_time_cost_sec", labels)
        self._t_ship = reg.histogram("update_ship_time_cost_sec", labels)
        # buffer-depth/staleness gauges: every mutation happens under
        # self._lock, so set() from _sync_gauges_locked is exact — these
        # are what /healthz and a scraper watch to catch a stuck
        # pipeline (staleness pegged at the semaphore bound, forward
        # buffer climbing toward ForwardBufferFull)
        self._g_forward_buf = reg.gauge("worker_forward_buffer_depth",
                                        labels)
        self._g_post_buf = reg.gauge("worker_post_forward_buffer_depth",
                                     labels)
        self._g_staleness = reg.gauge("worker_staleness", labels)
        # periodic expiry sweep — ingestion-piggybacked expiry alone never
        # fires once the loaders die (see _sweep_loop)
        self._sweep_stop = threading.Event()
        self._sweep_thread = threading.Thread(
            target=self._sweep_loop, daemon=True, name="worker-expiry-sweep")
        self._sweep_thread.start()

    # --- control plane ---------------------------------------------------

    def configure_parameter_servers(self, init_method: str, init_params: dict,
                                    admit_probability: float,
                                    weight_bound: float,
                                    enable_weight_bound: bool = True):
        # remembered so a re-resolved (restarted) PS can be re-armed
        self._last_configure = (init_method, init_params, admit_probability,
                                weight_bound, enable_weight_bound)
        for c in self.ps_clients:
            c.configure(init_method, init_params, admit_probability,
                        weight_bound, enable_weight_bound)

    def register_optimizer(self, config: dict):
        self._last_optimizer = config
        for c in self.ps_clients:
            c.register_optimizer(
                config,
                feature_index_prefix_bit=self.schema.feature_index_prefix_bit,
            )

    # --- routing control plane -------------------------------------------

    @property
    def routing(self):
        """The current :class:`~persia_tpu.routing.RoutingTable`
        (immutable; an atomic reference read)."""
        return self._routing.table

    @property
    def routing_epoch(self) -> int:
        return self._routing.epoch

    @property
    def routing_window(self):
        """``(table, prev)`` — the live table plus the double-read
        predecessor while a migration window is open (None once
        drained). Read atomically under the routing holder's lock
        (``RoutingHolder.window``): consumers that must agree with
        this worker's shard view across reshard epochs (the serving
        tier's online delta subscriber) would otherwise race a cutover
        swap into a torn pair."""
        return self._routing.window()

    def apply_routing(self, table, ps_clients=None) -> bool:
        """Atomically swap in a successor routing table (and, on
        scale-out/in, the replica client list) mid-traffic. Epoch-
        checked: a stale or duplicate publish is a no-op (returns
        False). The predecessor stays readable through the double-read
        window until :meth:`close_routing_window`; in-flight batches
        split under the old epoch keep their cached shard groups and
        settle against donors, which retain moved rows until the
        migration's finalize."""
        dropped = []
        with self._ps_lock:
            if table.epoch <= self._routing.epoch:
                return False
            new_clients = (list(ps_clients) if ps_clients is not None
                           else self.ps_clients)
            if table.num_replicas > len(new_clients):
                raise ValueError(
                    f"routing epoch {table.epoch} references "
                    f"{table.num_replicas} replicas but worker has "
                    f"{len(new_clients)} PS clients")
            applied = self._routing.apply(table)
            if applied:
                # the client list only changes WITH its table: a late
                # lower-epoch publish must not shrink the live list out
                # from under a newer epoch's routing
                if self.ps_clients is not new_clients:
                    keep = set(map(id, new_clients))
                    dropped = [c for c in self.ps_clients
                               if id(c) not in keep]
                self.ps_clients = new_clients
                self.replica_size = len(new_clients)
        for c in dropped:
            # a replaced client's sockets must not leak one generation
            # per reshard (same discipline as _refresh_ps_clients;
            # racing callers simply redial)
            close = getattr(getattr(c, "client", None), "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass
        if applied and self._fanout is None and len(self.ps_clients) > 1:
            self._fanout = ThreadPoolExecutor(
                max_workers=min(2 * len(self.ps_clients), 32),
                thread_name_prefix="ps-fanout")
        if applied:
            _logger.info("routing epoch %d applied (%d replicas, %d slots)",
                         table.epoch, table.num_replicas, table.num_slots)
        return applied

    def close_routing_window(self):
        """End the double-read window (migration drained)."""
        self._routing.close_window()

    def _await_epoch(self, min_epoch: int, deadline: float,
                     retry_interval: float = 0.25):
        """Wait for the routing cell to reach ``epoch >= min_epoch`` —
        the worker side of the reshard freeze window — returning EARLY
        every ``retry_interval`` so the settle loops can retry at the
        CURRENT epoch: an aborted migration unfreezes its donors
        without ever publishing the demanded epoch, and the old routing
        is then fully valid again. Pulls from ``routing_fetch`` when
        provided (coordinator KV); a pulled table goes through
        :meth:`apply_routing` (epoch + client-count guarded), growing
        the client list through the resolver when a scale-out table
        references replicas this worker has not dialed yet."""
        t_next_retry = time.monotonic() + retry_interval
        while self._routing.epoch < min_epoch:
            if self._routing_fetch is not None:
                try:
                    t = self._routing_fetch()
                    if t is not None and t.epoch > self._routing.epoch:
                        try:
                            self.apply_routing(t)
                            continue
                        except ValueError:
                            # the pulled table references replicas we
                            # have no clients for: re-resolve the fleet
                            if self._ps_resolver is not None:
                                clients = list(self._ps_resolver())
                                if len(clients) >= t.num_replicas:
                                    self.apply_routing(t,
                                                       ps_clients=clients)
                                    continue
                except Exception:
                    pass
            now = time.monotonic()
            if now > deadline:
                raise RuntimeError(
                    f"routing epoch {min_epoch} demanded by a resharding "
                    f"PS never arrived within the stale-retry budget")
            if now >= t_next_retry:
                return  # let the caller retry at the current epoch
            time.sleep(0.01)

    def _stale_deadline(self) -> float:
        from persia_tpu import knobs

        return time.monotonic() + float(
            knobs.get("PERSIA_RESHARD_STALE_RETRY_SEC"))

    # --- data-loader side ------------------------------------------------

    def put_batch(self, id_type_features: List[IDTypeFeature]) -> int:
        """Ingest a pre-lookup batch; returns its ref_id
        (reference: forward_batched, mod.rs:656-701)."""
        self._expire_stale()
        with self._lock:
            if len(self._forward_id_buffer) >= self.forward_buffer_size:
                raise ForwardBufferFull(
                    f"forward buffer full ({self.forward_buffer_size})"
                )
            ref_id = self._next_ref_id
            self._next_ref_id += 1
        feats = mw.preprocess_batch(id_type_features, self.schema)
        with self._lock:
            self._forward_id_buffer[ref_id] = (feats, time.monotonic())
            self._sync_gauges_locked()
        return ref_id

    def _sync_gauges_locked(self):
        """Mirror buffer depths + staleness into the registry gauges.
        Caller holds self._lock, so the values are consistent."""
        self._g_forward_buf.set(len(self._forward_id_buffer))
        self._g_post_buf.set(len(self._post_forward_buffer))
        self._g_staleness.set(self.staleness)

    def _expire_stale(self):
        horizon = time.monotonic() - self.buffered_data_expired_sec
        with self._lock:
            for buf in (self._forward_id_buffer, self._post_forward_buffer):
                expired = [r for r, item in buf.items() if item[-1] < horizon]
                for r in expired:
                    del buf[r]
                if expired and buf is self._post_forward_buffer:
                    # each post-forward entry holds one staleness permit
                    # (taken at lookup, normally released by
                    # update_gradients); a dead trainer's entries must
                    # release theirs or the counter stays elevated forever
                    self.staleness -= len(expired)
                if expired:
                    _logger.warning("expired %d stale buffered batches",
                                    len(expired))
            self._sync_gauges_locked()

    def _sweep_loop(self):
        """Background expiry, matching the C++ binary's periodic sweep
        (native/src/worker_server.cc) and the reference's tokio interval
        task (embedding_worker_service/mod.rs:991-1029). Without it, a
        worker whose data-loaders/trainers died keeps dead buffer entries
        (and their staleness counts) until the next ingest — which for a
        dead pipeline never comes."""
        interval = max(1.0, min(self.buffered_data_expired_sec / 4.0, 30.0))
        while not self._sweep_stop.wait(interval):
            try:
                self._expire_stale()
            except Exception:
                _logger.exception("expiry sweep failed")

    def close(self):
        """Stop the background sweep (tests; services just exit)."""
        self._sweep_stop.set()

    # --- observability ---------------------------------------------------

    STAGE_NAMES = ("preprocess", "rpc", "postprocess", "aggregate", "ship")

    def _stage_hists(self):
        return {
            "preprocess": self._t_preprocess,
            "rpc": self._t_rpc,
            "postprocess": self._t_postprocess,
            "aggregate": self._t_aggregate,
            "ship": self._t_ship,
        }

    def stage_snapshot(self) -> Dict[str, tuple]:
        """(count, total_sec) per worker-cycle stage. The histograms are
        process-shared through the metrics registry, so benchmarks diff
        two snapshots to attribute time to a bounded region."""
        return {k: h.snapshot() for k, h in self._stage_hists().items()}

    @staticmethod
    def stage_breakdown(before: Dict[str, tuple],
                        after: Dict[str, tuple]) -> Dict[str, dict]:
        """Per-stage {count, total_sec, avg_ms} between two snapshots."""
        out = {}
        for k in before:
            n = after[k][0] - before[k][0]
            sec = after[k][1] - before[k][1]
            out[k] = {"count": n, "total_sec": round(sec, 4),
                      "avg_ms": round(sec / n * 1e3, 3) if n else 0.0}
        return out

    # --- trainer side ----------------------------------------------------

    def lookup(self, ref_id: int, training: bool = True) -> Dict[str, object]:
        """Look up a previously-ingested batch by ref_id
        (reference: forward_batch_id, mod.rs:1031-1074)."""
        with self._lock:
            item = self._forward_id_buffer.pop(ref_id, None)
            self._sync_gauges_locked()
        if item is None:
            raise KeyError(f"ref_id {ref_id} not in forward buffer")
        feats, enter_time = item
        try:
            result, groups, fwd_epoch = self._lookup_feats(feats,
                                                           training)
        except BaseException:
            # restore the entry so a retry after PS recovery can still
            # find its batch (the client's lookup retry contract,
            # reference forward.rs:708-761)
            with self._lock:
                self._forward_id_buffer[ref_id] = (feats, enter_time)
                self._sync_gauges_locked()
            raise
        if training:
            with self._lock:
                # cache the shard groups so the gradient path reuses the
                # forward split instead of re-hashing every sign; the
                # epoch stamp lets the update path detect a reshard
                # that landed mid-pipeline and re-split instead of
                # shipping by a stale table (see _update_gradients_inner)
                self._post_forward_buffer[ref_id] = (
                    feats, (groups, fwd_epoch), time.monotonic())
                self.staleness += 1
                self._sync_gauges_locked()
        return result

    def lookup_direct(
        self, id_type_features: List[IDTypeFeature], training: bool = False
    ) -> Dict[str, object]:
        """One-shot preprocess+lookup without buffers — the inference/eval
        path (reference: forward_batched_direct, mod.rs:1076-1107)."""
        # (result only; the shard split and its epoch are discarded)
        feats = mw.preprocess_batch(id_type_features, self.schema)
        return self._lookup_feats(feats, training)[0]

    def lookup_direct_training(
        self, id_type_features: List[IDTypeFeature]
    ) -> Tuple[int, Dict[str, object]]:
        """Preprocess+lookup keeping gradient state — the synchronous
        training path used by the in-process e2e slice."""
        ref_id = self.put_batch(id_type_features)
        return ref_id, self.lookup(ref_id, training=True)

    def _lookup_feats(self, feats, training: bool
                      ) -> Tuple[Dict[str, object], list, int]:
        """Preprocess + fan-out lookup; returns (per-feature results,
        the shard groups, and the routing epoch the split used — the
        update path re-splits when the epoch moved)."""
        if self.monitor is not None:
            for f in feats:
                self.monitor.observe(f.name, f.distinct_signs)
        routing = self._routing.table
        with self._t_preprocess.timer(), tracing.span("worker/preprocess"):
            groups = mw.shard_split(feats, self.schema,
                                    routing.num_replicas, routing=routing)
            mats = mw.alloc_lookup_mats(feats, self.schema)
        # fan-out pool threads have no thread-local trace context — the
        # do_lookup_* closures capture the active worker/rpc span (they
        # run inside it) so per-(shard,dim) PS calls (and through the
        # RPC envelope, the PS handler spans) keep their parentage
        tctx = None

        def ps_lookup(g):
            with tracing.span("worker/ps_lookup", ctx=tctx, shard=g.shard,
                              dim=g.dim, n=len(g.signs)):
                try:
                    return self.ps_clients[g.shard].lookup(g.signs, g.dim,
                                                           training)
                except Exception as e:
                    return self._settle_stale_lookup(g, training, e)

        def do_lookup_serialized():
            nonlocal tctx
            tctx = tracing.current_context()
            # legacy plane: gather every shard's result, then scatter
            if self._fanout is None or len(groups) <= 1:
                results = [ps_lookup(g) for g in groups]
            else:
                results = list(self._fanout.map(ps_lookup, groups))
            for g, res in zip(groups, results):
                mw.scatter_group(mats, g, res)

        def do_lookup_streaming():
            nonlocal tctx
            tctx = tracing.current_context()
            # one fan-out task per REPLICA; inside it, the replica's
            # (shard,dim) groups multiplex on the thread's one
            # connection (PsClient.lookup_future, tag-matched) and each
            # result scatters the moment it arrives — no gather
            # barrier, and a slow shard never convoys the fast ones.
            # Below MUX_MIN_GROUPS the per-request dispatch-pool cost
            # on the server outweighs the saved connections (measured),
            # so few-group replicas run one blocking task per group
            # instead — still scatter-on-completion. Groups partition
            # the distinct signs, so cross-thread scatters are
            # disjoint.
            by_shard: Dict[int, list] = {}
            for g in groups:
                by_shard.setdefault(g.shard, []).append(g)

            def run_group(g):
                mw.scatter_group(mats, g, ps_lookup(g))

            def run_shard_mux(gs):
                client = self.ps_clients[gs[0].shard]

                def settle(g, resolve):
                    try:
                        return resolve()
                    except Exception as e:
                        return self._settle_stale_lookup(g, training, e)

                with tracing.span("worker/ps_lookup_mux", ctx=tctx,
                                  shard=gs[0].shard, groups=len(gs)):
                    pend = []
                    for g in gs:
                        if len(pend) >= self.MUX_WINDOW:
                            pg, resolve = pend.pop(0)
                            mw.scatter_group(mats, pg, settle(pg, resolve))
                        pend.append(
                            (g, client.lookup_future(g.signs, g.dim,
                                                     training)))
                    for g, resolve in pend:
                        mw.scatter_group(mats, g, settle(g, resolve))

            tasks = []
            for gs in by_shard.values():
                can_mux = hasattr(self.ps_clients[gs[0].shard],
                                  "lookup_future")
                if can_mux and len(gs) >= self.MUX_MIN_GROUPS:
                    tasks.append((run_shard_mux, gs))
                else:
                    tasks.extend((run_group, g) for g in gs)
            if self._fanout is None or len(tasks) <= 1:
                for fn, arg in tasks:
                    fn(arg)
                return
            futures = [self._fanout.submit(fn, arg) for fn, arg in tasks]
            for f in futures:
                f.result()

        # retries re-scatter every group into the same mats (idempotent
        # row overwrites), so a mid-fan-out failure is safe either way
        do_lookup = (do_lookup_streaming if self.streaming
                     else do_lookup_serialized)
        with self._t_rpc.timer(), tracing.span("worker/rpc",
                                               groups=len(groups)):
            self._with_ps_retry(do_lookup)
        with self._t_postprocess.timer(), tracing.span("worker/postprocess"):
            out = {}
            for feat, mat in zip(feats, mats):
                slot = self.schema.get_slot(feat.name)
                out[feat.name] = mw.postprocess_feature(feat, slot, mat)
        return out, groups, routing.epoch

    def update_gradients(
        self, ref_id: int, grads: Dict[str, np.ndarray],
        loss_scale: float = 1.0,
    ):
        """Route model gradients back to the parameter servers
        (reference: update_gradient_batched, mod.rs:1109-1129)."""
        with self._lock:
            item = self._post_forward_buffer.pop(ref_id, None)
            if item is not None:
                self.staleness -= 1
            self._sync_gauges_locked()
        if item is None:
            raise KeyError(f"ref_id {ref_id} not in post-forward buffer")
        try:
            self._update_gradients_inner(ref_id, item, grads, loss_scale)
        except BaseException:
            # restore so the trainer's retry after PS recovery still finds
            # the batch. Shard groups that already applied before the
            # failure may re-apply on retry (fresh dedup ids per call) —
            # a rare, bounded imprecision async sparse SGD tolerates.
            with self._lock:
                self._post_forward_buffer[ref_id] = item
                self.staleness += 1
                self._sync_gauges_locked()
            raise

    def _update_gradients_inner(self, ref_id, item, grads, loss_scale):
        feats, fwd, _ = item
        fwd_groups, fwd_epoch = (fwd if isinstance(fwd, tuple)
                                 else (fwd, self._routing.epoch))
        if fwd_groups is not None and fwd_epoch != self._routing.epoch:
            # a reshard cut over between this batch's forward and its
            # gradient return: the cached forward split routes by a
            # RETIRED table. Shipping by it would land moved signs on a
            # donor whose capture already disarmed (post-finalize, or a
            # restarted donor that lost its freeze state with the
            # process) — silently unreachable under the live table,
            # i.e. lost updates. Drop the cache and re-split below.
            _logger.info(
                "gradient return for ref %d crosses routing epochs "
                "(%d -> %d); re-splitting by the live table", ref_id,
                fwd_epoch, self._routing.epoch)
            fwd_groups = None
        # validate up front: a missing gradient must fail BEFORE any
        # group ships (the streaming path ships incrementally)
        for feat in feats:
            if feat.name not in grads:
                raise KeyError(f"missing gradient for feature {feat.name!r}")
        if not self.streaming or self._fanout is None:
            self._update_gradients_serialized(feats, fwd_groups, grads,
                                              loss_scale)
            return
        routing = self._routing.table
        groups = fwd_groups if fwd_groups is not None else mw.shard_split(
            feats, self.schema, routing.num_replicas, routing=routing)

        def group_by_last(groups):
            # a group is shippable once its LAST feature (feature_idx
            # is nondecreasing) has aggregated
            by_last: Dict[int, list] = {}
            for g in groups:
                last_fi = (int(g.feature_idx[-1]) if len(g.feature_idx)
                           else 0)
                by_last.setdefault(last_fi, []).append(g)
            return by_last

        by_last = group_by_last(groups)
        if len(by_last) <= 1:
            # uniform-dim schema: every group waits for the last feature
            # anyway, so "streaming" would only interleave gather with
            # ship threads for no overlap — the batch path is strictly
            # better
            self._update_gradients_serialized(feats, fwd_groups, grads,
                                              loss_scale)
            return

        def do_update_streaming():
            # runs inside the worker/update_stream span — capture it so
            # the fan-out ship threads parent their spans to it
            tctx = tracing.current_context()
            live = self._live_table_if_moved(routing)
            ship = by_last if live is None else group_by_last(
                mw.shard_split(feats, self.schema, live.num_replicas,
                               routing=live))
            futures = []
            per_feature: list = [None] * len(feats)
            agg_sec = 0.0
            for fi, feat in enumerate(feats):
                t0 = time.perf_counter()
                per_feature[fi] = mw.aggregate_gradients(
                    feat, self.schema.get_slot(feat.name), grads[feat.name],
                    loss_scale)
                ready = [(g, mw.gather_group_grads(g, per_feature))
                         for g in ship.get(fi, ())]
                agg_sec += time.perf_counter() - t0
                # ship already-aggregated groups while the remaining
                # features are still aggregating (fan-out threads do the
                # blocking sends; aggregation continues on this thread)
                for g, gmat in ready:
                    futures.append(self._fanout.submit(
                        self._ship_group, g.shard, g.signs, gmat, g.dim,
                        tctx))
            self._t_aggregate.observe(agg_sec)
            with self._t_ship.timer():
                for f in futures:
                    f.result()

        # on retry the whole closure re-runs: groups that applied before
        # the failure may re-apply (fresh dedup ids per call) — the same
        # rare, bounded imprecision the restore-path already documents
        with tracing.span("worker/update_stream", groups=len(groups)):
            self._with_ps_retry(do_update_streaming)

    def _ship_group(self, shard, signs, gmat, dim, tctx=None):
        with tracing.span("worker/ps_update", ctx=tctx, shard=shard,
                          dim=dim, n=len(signs)):
            try:
                self.ps_clients[shard].update_gradients(signs, gmat, dim)
            except Exception as e:
                self._settle_stale_update(signs, gmat, dim, e)

    # --- reshard cutover settlement --------------------------------------

    def _settle_stale(self, signs, exc, ship_fn, prepare_fn=None):
        """The one bounce-retry protocol behind every write path: a
        shipment bounced with routing_stale (its slots froze for
        migration) re-splits ONLY ITSELF by the current table and
        re-issues per new owner — applied groups are untouched, so
        nothing double-counts, and the migration replays every
        captured row to the target before the new epoch publishes, so
        a re-routed shipment lands on a replica that already owns the
        rows. The epoch wait returns periodically (see
        :meth:`_await_epoch`) so an ABORTED migration — donors
        unfrozen, demanded epoch never published — settles by plain
        retry at the current epoch. ``ship_fn(replica, sel)`` issues
        the per-replica RPC for the selected sign indices; chained
        bounces (a second reshard mid-retry) loop until the deadline.

        A CONNECTION failure mid-settle (a replica SIGKILLed while the
        bounce waited out a cutover — the chaos-reshard matrix's
        donor-kill cells) is handled HERE, not re-raised: the failed
        portion stays pending, the client tier recovers (re-resolve /
        re-arm), and the next round re-splits it by the then-current
        table. Propagating it instead hands control to the caller's
        whole-fan-out retry, which re-ships its PRE-RESHARD shard
        groups — the moved signs would land on the restarted donor's
        stale, no-longer-routed copies (the restart cleared its freeze
        state) and read back as lost updates, while the portions that
        already applied double-apply. The same applies when the
        ORIGINAL failure is a transport loss (the donor died with its
        freeze state, so nothing ever bounced): the portion settles
        here at the current epoch. Re-raises anything that is neither
        a stale bounce nor a transport loss; a portion that never
        settles because its replica stays down re-raises the LAST
        transport error at the deadline, so legacy catch clauses
        (ConnectionError) still hold for a permanently dead fleet."""
        from persia_tpu.routing import is_routing_stale

        last_conn_exc = None
        min_epoch = is_routing_stale(exc)
        if min_epoch is None:
            if not isinstance(exc, (ConnectionError, OSError)):
                raise exc
            last_conn_exc = exc
            min_epoch = self._routing.epoch
        deadline = self._stale_deadline()
        # ``prepare_fn(replica, sel)`` runs before ship_fn ONLY once a
        # replica restart is in play (the original failure was a
        # transport loss, or a round hit one / re-armed a blank
        # replica): the restored store lacks rows that were created but
        # never durably updated, and the update path must re-create
        # them first. Ordinary stale bounces skip it — one RPC per
        # round, and deliberately evicted rows are not resurrected.
        need_prepare = last_conn_exc is not None
        pending = np.arange(len(signs), dtype=np.int64)
        while len(pending):
            if time.monotonic() > deadline:
                if last_conn_exc is not None:
                    raise last_conn_exc
                raise RuntimeError(
                    "routing_stale bounces did not settle within the "
                    "stale-retry budget (a replica is refusing writes "
                    "for slots the current table routes to it)")
            self._await_epoch(min_epoch, deadline)
            shards = self._routing.table.replica_of(signs[pending])
            bounced = []
            conn_failed = False
            for r in np.unique(shards):
                sel = pending[np.nonzero(shards == r)[0]]
                try:
                    if need_prepare and prepare_fn is not None:
                        prepare_fn(int(r), sel)
                    ship_fn(int(r), sel)
                except Exception as e:
                    me = is_routing_stale(e)
                    if me is not None:
                        min_epoch = max(min_epoch, me)
                        bounced.append(sel)
                        continue
                    if isinstance(e, (ConnectionError, OSError)):
                        conn_failed = True
                        last_conn_exc = e
                        bounced.append(sel)
                        continue
                    from persia_tpu.rpc import RpcError

                    if (isinstance(e, RpcError)
                            and self._rearm_unready_clients()):
                        # application error from a restored-but-blank
                        # replica (restore loads rows, not the
                        # optimizer): re-armed in place — retry the
                        # portion here for the same reason as the
                        # transport case (the caller's whole-fan-out
                        # retry ships stale groups)
                        need_prepare = True
                        bounced.append(sel)
                        continue
                    raise
            if conn_failed:
                need_prepare = True
                # restart recovery scoped to the failed portion only
                try:
                    if self._ps_resolver is not None:
                        self._refresh_ps_clients()
                    else:
                        self._rearm_unready_clients()
                except Exception:
                    pass  # replica still down; the deadline bounds us
            pending = (np.concatenate(bounced) if bounced
                       else pending[:0])
            if len(pending):
                # a bounce at the CURRENT epoch means the freeze window
                # is still closing — back off briefly; a downed replica
                # needs its supervisor's restart window
                time.sleep(0.2 if conn_failed else 0.005)

    def _settle_stale_lookup(self, group, training: bool, exc):
        signs, dim = group.signs, group.dim
        res = np.empty((len(signs), dim), np.float32)

        def ship(r, sel):
            res[sel] = self.ps_clients[r].lookup(signs[sel], dim,
                                                 training)

        self._settle_stale(signs, exc, ship)
        return res

    def _settle_stale_update(self, signs, gmat, dim, exc):
        # prepare (recovery rounds only): a restarted replica restored
        # only its DURABLE rows — one this batch's forward created but
        # never updated died with the old process, and the PS silently
        # drops gradients for missing rows (the eviction-race miss
        # counter's designed behavior), so the retried update would ack
        # without applying. Re-create through the sanctioned path (a
        # training lookup honors admission) before the gradient.
        self._settle_stale(
            signs, exc,
            lambda r, sel: self.ps_clients[r].update_gradients(
                signs[sel], gmat[sel], dim),
            prepare_fn=lambda r, sel: self.ps_clients[r].lookup(
                signs[sel], dim, True))

    def _update_gradients_serialized(self, feats, fwd_groups, grads,
                                     loss_scale):
        """Legacy plane: aggregate everything, then ship every group."""
        with self._t_aggregate.timer(), tracing.span("worker/aggregate"):
            per_feature = [
                mw.aggregate_gradients(feat, self.schema.get_slot(feat.name),
                                       grads[feat.name], loss_scale)
                for feat in feats
            ]
            routing = self._routing.table
            shard_groups = mw.shard_gradients(
                feats, self.schema, per_feature, routing.num_replicas,
                groups=fwd_groups, routing=routing,
            )
        def do_update():
            # runs inside the worker/ship span — capture it so fan-out
            # threads parent their per-shard spans to it
            tctx = tracing.current_context()
            live = self._live_table_if_moved(routing)
            ship = shard_groups if live is None else mw.shard_gradients(
                feats, self.schema, per_feature, live.num_replicas,
                routing=live)
            if self._fanout is None or len(ship) <= 1:
                for shard, dim, signs, g in ship:
                    self._ship_group(shard, signs, g, dim, tctx)
                return
            futures = [
                self._fanout.submit(self._ship_group, shard, signs, g, dim,
                                    tctx)
                for shard, dim, signs, g in ship
            ]
            for f in futures:
                f.result()

        with self._t_ship.timer(), tracing.span("worker/ship"):
            self._with_ps_retry(do_update)

    def _live_table_if_moved(self, split_by):
        """The live routing table when it is no longer ``split_by``, the
        table an update's shard groups were split by; else None. Asked
        at the top of every fan-out attempt: a shipment that gives up in
        :meth:`_settle_stale` (one call's retry ladder against a dead
        replica can outlast the whole stale-retry budget) comes back
        through :meth:`_with_ps_retry`, and a migration may have cut
        over and finalized meanwhile. Groups split by the retired table
        would land the moved signs on the donor's disarmed, unreachable
        copies, acked: lost updates (the reshard kill matrix's donor
        cells)."""
        live = self._routing.table
        if live.epoch == split_by.epoch:
            return None
        _logger.info("update fan-out retried across routing epochs "
                     "(%d -> %d); re-splitting by the live table",
                     split_by.epoch, live.epoch)
        return live

    def _with_ps_retry(self, fn):
        """Run a PS fan-out, recovering from replica failures
        (reference mod.rs:1320-1333):

        - connection-level failure (client retries already exhausted):
          re-resolve the replica list from the coordinator when a
          resolver exists (restart on a NEW port), else re-arm unready
          replicas in place (a quick restart on the old address that the
          client silently redialed), then retry once;
        - application error (RpcError): a restarted PS serves RPCs again
          but lost its store config — if any replica reports not-ready,
          re-arm it and retry once; otherwise the error is genuine and
          propagates.
        """
        from persia_tpu.rpc import RpcError

        try:
            return fn()
        except (ConnectionError, OSError):
            if self._ps_resolver is not None:
                self._refresh_ps_clients()
            else:
                self._rearm_unready_clients()
            return fn()
        except RpcError:
            if not self._rearm_unready_clients():
                raise
            return fn()

    def _rearm_unready_clients(self) -> bool:
        """Re-push the remembered store config + optimizer to replicas
        that report not-ready (fresh restarts). Healthy replicas are left
        untouched — re-registering an optimizer replaces its server-side
        state (e.g. SparseAdam's bias-correction powers), which must
        never happen to a PS that did not fail. Returns True if any
        replica was re-armed."""
        with self._rearm_lock:
            return self._rearm_unready_locked()

    def _rearm_unready_locked(self) -> bool:
        rearmed = False
        for c in list(self.ps_clients):
            ready_fn = getattr(c, "ready_for_serving", None)
            if ready_fn is None:
                continue
            try:
                if ready_fn():
                    continue
            except Exception:
                continue  # still down: transport recovery handles it
            try:
                cfg = getattr(self, "_last_configure", None)
                if cfg is not None:
                    c.configure(*cfg)
                opt = getattr(self, "_last_optimizer", None)
                if opt is not None:
                    c.register_optimizer(
                        opt,
                        feature_index_prefix_bit=(
                            self.schema.feature_index_prefix_bit),
                    )
                rearmed = True
                _logger.warning("re-armed restarted PS %s",
                                getattr(c, "addr", c))
            except Exception as e:
                _logger.warning("re-arm of %s failed: %s",
                                getattr(c, "addr", c), e)
        return rearmed

    def _refresh_ps_clients(self):
        new_clients = list(self._ps_resolver())
        if len(new_clients) != self.replica_size:
            raise RuntimeError(
                f"PS re-resolution returned {len(new_clients)} replicas, "
                f"expected {self.replica_size} (shard routing would change)"
            )
        with self._ps_lock:
            old_clients = self.ps_clients
            self.ps_clients = new_clients
        for c in old_clients:
            close = getattr(getattr(c, "client", None), "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass
        _logger.warning("refreshed PS client list after connection failure")
        self._rearm_unready_clients()

    # --- raw row access (inference hot-row cache miss path) --------------

    def lookup_signs(self, signs: np.ndarray, dim: int) -> np.ndarray:
        """Eval-mode row lookup for ALREADY-PREPROCESSED distinct signs
        (the serving tier runs dedup/hashstack/prefix itself and sends
        only its cache misses here — one deduplicated call instead of a
        full per-request lookup fan-out). Shard-routed by the same
        slot split as every other lookup; absent signs zero-fill
        (PS eval semantics) and are NEVER created — the serving path is
        read-only. During a reshard's double-read window, signs whose
        owner just changed are read from BOTH owners: the new owner
        wins unless it answers all-zero (row not yet visible there)
        while the previous owner still has it — so an in-flight or
        out-of-band epoch swap never serves a transient zero for a row
        the fleet durably holds."""
        routing = self._routing.table
        prev = self._routing.prev
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        out = np.zeros((len(signs), dim), np.float32)
        if len(signs) == 0:
            return out
        shards = routing.replica_of(signs)
        groups = [np.nonzero(shards == r)[0] for r in np.unique(shards)]
        replicas = [int(shards[sel[0]]) for sel in groups]

        tctx = tracing.current_context()

        def fetch_one(r, sel):
            with tracing.span("worker/ps_lookup", ctx=tctx, shard=r,
                              dim=dim, n=len(sel)):
                return self.ps_clients[r].lookup(signs[sel], dim, False)

        def fetch_all():
            if self._fanout is None or len(groups) <= 1:
                return [fetch_one(r, sel)
                        for r, sel in zip(replicas, groups)]
            return list(self._fanout.map(
                lambda rs: fetch_one(*rs), zip(replicas, groups)))

        with self._t_rpc.timer():
            results = self._with_ps_retry(fetch_all)
        for sel, rows in zip(groups, results):
            out[sel] = rows
        if prev is not None and prev.num_slots == routing.num_slots:
            # double-read: only the moved signs that read back empty
            moved = np.nonzero(prev.replica_of(signs) != shards)[0]
            if len(moved):
                empty = moved[~out[moved].any(axis=1)]
                if len(empty):
                    old_owner = prev.replica_of(signs[empty])
                    for r in np.unique(old_owner):
                        sel = empty[np.nonzero(old_owner == r)[0]]
                        try:
                            out[sel] = self.ps_clients[int(r)].lookup(
                                signs[sel], dim, False)
                        except Exception:
                            pass  # donor already gone: keep the zeros
        return out

    # --- checkpoint fan-out ----------------------------------------------

    # --- raw row access (device-cache miss/write-back path) --------------

    def lookup_rows_with_state(self, signs: np.ndarray, dim: int,
                               default_state: float = 0.0):
        """Per-sign rows INCLUDING optimizer state, routed by the same
        farmhash shard split as normal lookups. The batched ``lookup``
        first creates+initializes any missing entries exactly like a
        training lookup; the batched ``get_entries`` then reads the full
        vecs (value + state) — one extra round trip per replica, not per
        sign — so a re-admitted sign keeps its accumulator history.
        Admission-rejected signs stay absent: value 0, state
        ``default_state``. Returns (vals (n, dim) f32, state (n, dim)
        f32; non-shared Adagrad state width == dim, the only optimizer
        the device cache admits)."""
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        n = len(signs)
        width = 2 * dim  # value + per-element accumulator
        vals = np.zeros((n, dim), np.float32)
        state = np.full((n, dim), default_state, np.float32)
        shards = self._routing.table.replica_of(signs)
        groups = [np.nonzero(shards == r)[0] for r in np.unique(shards)]
        replicas = [int(shards[sel[0]]) for sel in groups]

        def fetch_one(r, sel):
            client = self.ps_clients[r]
            client.lookup(signs[sel], dim, True)
            return client.get_entries(signs[sel], width)

        def fetch_all():
            # miss import sits on the training critical path: overlap
            # the per-replica round trips like the normal lookup fan-out
            if self._fanout is None or len(groups) <= 1:
                return [fetch_one(r, sel)
                        for r, sel in zip(replicas, groups)]
            return list(self._fanout.map(
                lambda rs: fetch_one(*rs), zip(replicas, groups)))

        with self._t_rpc.timer(), tracing.span(
                "worker/rows_with_state", n=n, replicas=len(groups)):
            results = self._with_ps_retry(fetch_all)
        for sel, (found, vecs) in zip(groups, results):
            hit = np.nonzero(found)[0]
            vals[sel[hit]] = vecs[hit, :dim]
            state[sel[hit]] = vecs[hit, dim:]
        return vals, state

    def set_rows(self, signs: np.ndarray, vecs: np.ndarray, dim: int):
        """Write full rows (value + optimizer state) back, shard-routed,
        one batched RPC per replica — the device cache's eviction
        write-back / flush_all."""
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        vecs = np.ascontiguousarray(vecs, dtype=np.float32)
        shards = self._routing.table.replica_of(signs)
        groups = [np.nonzero(shards == r)[0] for r in np.unique(shards)]
        replicas = [int(shards[sel[0]]) for sel in groups]

        def push_one(r, sel):
            try:
                self.ps_clients[r].set_entries(signs[sel], dim, vecs[sel])
            except Exception as e:
                # a write-back to frozen moving slots re-routes exactly
                # like a gradient shipment — the device cache's flushed
                # rows must land somewhere or eviction loses state
                self._settle_stale_set(signs[sel], vecs[sel], dim, e)

        def push_all():
            if self._fanout is None or len(groups) <= 1:
                for r, sel in zip(replicas, groups):
                    push_one(r, sel)
                return
            list(self._fanout.map(lambda rs: push_one(*rs),
                                  zip(replicas, groups)))

        self._with_ps_retry(push_all)

    def _settle_stale_set(self, signs, vecs, dim, exc):
        self._settle_stale(
            signs, exc,
            lambda r, sel: self.ps_clients[r].set_entries(
                signs[sel], dim, vecs[sel]))

    def dump(self, dirpath: str):
        from persia_tpu.checkpoint import dump_sharded
        from persia_tpu.pipeline import flush_backward_engines

        flush_backward_engines(self)
        t = self._routing.table
        dump_sharded(self.ps_clients[:t.num_replicas], dirpath, routing=t)

    def load(self, dirpath: str):
        from persia_tpu.checkpoint import load_sharded

        t = self._routing.table
        load_sharded(self.ps_clients[:t.num_replicas], dirpath, routing=t)
