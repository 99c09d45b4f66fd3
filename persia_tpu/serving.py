"""Online inference serving: the high-throughput predict path.

The reference serves through TorchServe: a PersiaHandler holds an
InferCtx, deserializes PersiaBatch bytes, does a direct embedding lookup
and a forward pass (examples/src/adult-income/serve_handler.py +
persia/ctx.py:1077-1133). Here the equivalent is a self-contained
:class:`InferenceServer` on the framework RPC: ``predict`` takes
PersiaBatch bytes (the same PTB2 wire clients already produce) and
returns the model outputs; embedding workers are resolved via
:mod:`persia_tpu.service_discovery`.

Beyond the reference's one-request-one-forward handler, the server has a
throughput path built from three pieces (all opt-in, all off by default
so the legacy serialized behavior is bit-identical):

- **Adaptive micro-batching** (``max_batch_rows > 0``): concurrent
  ``predict`` requests are coalesced by a dispatcher thread into ONE
  merged PersiaBatch -> one embedding lookup -> one jitted forward, and
  the per-request row slices are scattered back. The linger window
  (``max_wait_us``) is adaptive: it only waits for stragglers when the
  recent coalescing EWMA says traffic is actually concurrent, so an idle
  server adds no latency to serial requests.
- **Shape bucketing**: merged batches are padded with empty rows (no
  signs, zero dense features) up to a small set of bucket sizes, so the
  jitted eval step compiles once per bucket instead of retracing for
  every distinct coalesced request count. Padding rows cannot leak:
  summed slots pool zero ids to zero vectors, raw slots emit all-padding
  index rows, and only the first ``rows`` outputs are scattered back.
- **Cross-request sign dedup + a read-only hot-row TTL cache**
  (``cache_rows > 0``): the merged batch is preprocessed locally
  (dedup/hashstack/prefix — the same middleware transforms the worker
  would run), distinct post-transform signs are served from an in-process
  LRU, and only the misses travel to the embedding worker through ONE
  deduplicated ``lookup_signs`` RPC per dim. Entries expire after
  ``cache_ttl_sec`` so rows hot-loaded by :mod:`persia_tpu.inc_update`
  on the PS tier become visible within the TTL; the cache is never
  written by the serving path (read-only), so it cannot diverge from the
  PS beyond that staleness bound.

The embedding-row wire honors the mixed-precision codec policy
(``PERSIA_PS_WIRE_CODEC`` / ``--wire-codec``): miss-fetch rows travel
fp16 on the serving->worker hop and the worker->PS lookups ride the
negotiated PS codec — roughly half the row bytes per cache miss, with
the decode keyed on response metadata so any legacy peer keeps fp32.

Two further opt-in layers (both byte-identical-off, see
docs/ARCHITECTURE.md "Online learning loop & variant serving"):

- **Online delta subscription** (:meth:`InferenceServer.attach_delta_subscriber`
  / ``--inc-dir``): the hot-row cache subscribes to the trainer's
  incremental-update packet stream (:mod:`persia_tpu.online`) and
  upserts resident rows in place — versioned, TTL-independent,
  governed — making sign-to-servable latency a measured property
  (``serving_sign_to_servable_lag_sec``) instead of a TTL bound.
- **Multi-model variants** (:mod:`persia_tpu.variants`): N dense
  models over ONE worker/cache/PS fleet, per-request routing
  (explicit pin, route key, or a request field) through a
  deterministic weighted split, per-variant metrics/health/SLO
  isolation, and live add/remove/promote via the ``variant_admin``
  RPC (the k8s operator's ``POST /variants`` fans it out).

Serving counters use the reference's ``*_time_cost_sec`` metric style
and are exported through :mod:`persia_tpu.metrics` (labeled per server
port) plus a ``stats`` RPC for scrapers.

Typical wiring::

    server = InferenceServer(model, state, schema, worker_addrs,
                             port=8501, max_batch_rows=256,
                             cache_rows=1_000_000, cache_ttl_sec=30.0)
    server.serve_forever()

    client = InferenceClient("host:8501")
    preds = client.predict(persia_batch)
"""

import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence, Tuple

import msgpack
import numpy as np

from persia_tpu import knobs
from persia_tpu import tracing
from persia_tpu.config import EmbeddingSchema
from persia_tpu.ctx import InferCtx
from persia_tpu.data.batch import (
    MAX_BATCH_SIZE,
    IDTypeFeature,
    NonIDTypeFeature,
    PersiaBatch,
)
from persia_tpu.logger import get_default_logger
from persia_tpu.rpc import (
    RpcClient,
    RpcDeadlineExceeded,
    RpcError,
    RpcServer,
    pack_arrays,
    unpack_arrays,
)

# failures that degrade to zero-vector embeddings instead of failing the
# request: a circuit-open replica (RpcCircuitOpen is a ConnectionError),
# a shed deadline, transport loss/timeouts. Application errors (schema
# mismatch, bad payload) still fail the request — they would zero-fill
# forever, not transiently.
DEGRADABLE_ERRORS = (RpcDeadlineExceeded, ConnectionError, OSError)

_logger = get_default_logger(__name__)


# --- batch merging / padding (the micro-batcher's data plane) ------------


def _merge_id_features(feats: Sequence[IDTypeFeature]) -> IDTypeFeature:
    """CSR concatenation of the same feature across requests."""
    total_rows = sum(f.batch_size for f in feats)
    offsets = np.empty(total_rows + 1, np.uint32)
    offsets[0] = 0
    signs_parts: List[np.ndarray] = []
    pos, nnz = 1, 0
    for f in feats:
        bs = f.batch_size
        offsets[pos:pos + bs] = (
            f.offsets[1:].astype(np.int64) + nnz).astype(np.uint32)
        pos += bs
        nnz += int(f.offsets[-1])
        signs_parts.append(f.signs)
    signs = (np.concatenate(signs_parts) if nnz
             else np.empty(0, np.uint64))
    return IDTypeFeature.from_csr(feats[0].name, offsets, signs)


def merge_batches(
    batches: Sequence[PersiaBatch],
) -> Tuple[PersiaBatch, List[int]]:
    """Concatenate per-request batches into one batch + the row sizes
    needed to scatter predictions back. Labels are dropped (predict
    never reads them). Callers must pre-group by schema signature —
    every batch needs the same feature names/order and dense shapes."""
    sizes = [b.batch_size for b in batches]
    if len(batches) == 1:
        return batches[0], sizes
    id_feats = [
        _merge_id_features([b.id_type_features[i] for b in batches])
        for i in range(len(batches[0].id_type_features))
    ]
    non_id = [
        NonIDTypeFeature(
            np.concatenate([b.non_id_type_features[i].data
                            for b in batches]),
            name=batches[0].non_id_type_features[i].name)
        for i in range(len(batches[0].non_id_type_features))
    ]
    return PersiaBatch(id_feats, non_id_type_features=non_id,
                       requires_grad=False), sizes


def pad_batch(batch: PersiaBatch, target_rows: int) -> PersiaBatch:
    """Pad to ``target_rows`` with EMPTY samples: id features gain rows
    with zero signs (offsets repeat — nothing new is looked up, so the
    padding can never touch the PS or pollute the hot-row cache), dense
    features gain zero rows. Model outputs for padded rows are simply
    never scattered back."""
    extra = target_rows - batch.batch_size
    if extra <= 0:
        return batch
    id_feats = []
    for f in batch.id_type_features:
        offsets = np.concatenate([
            f.offsets,
            np.full(extra, f.offsets[-1], np.uint32),
        ])
        id_feats.append(IDTypeFeature.from_csr(f.name, offsets, f.signs))
    non_id = [
        NonIDTypeFeature(
            np.concatenate([
                x.data,
                np.zeros((extra,) + x.data.shape[1:], x.data.dtype),
            ]),
            name=x.name)
        for x in batch.non_id_type_features
    ]
    return PersiaBatch(id_feats, non_id_type_features=non_id,
                       requires_grad=False)


def _batch_signature(batch: PersiaBatch) -> tuple:
    """Merge-compatibility key: feature names/order + dense geometry."""
    return (
        tuple(f.name for f in batch.id_type_features),
        tuple((x.name, x.data.dtype.str, x.data.shape[1:])
              for x in batch.non_id_type_features),
    )


def default_buckets(max_rows: int) -> Tuple[int, ...]:
    """Power-of-two ladder up to ``max_rows`` (4 sizes): enough shape
    reuse that the eval step compiles a handful of times, small enough
    that fill ratio stays high."""
    out = []
    b = max_rows
    for _ in range(4):
        if b < 1:
            break
        out.append(b)
        b //= 2
    return tuple(sorted(set(out)))


# --- hot-row cache -------------------------------------------------------


class HotRowCache:
    """LRU of (dim, sign) -> embedding row with a TTL and a version.

    The predict path NEVER writes rows back; its only writer besides
    the miss-fetch ``put`` is the online delta subscriber
    (:mod:`persia_tpu.online`), which upserts RESIDENT rows in place
    via :meth:`apply_delta`. Consistency contract:

    - Every entry is a ``(row, expires, ver)`` tuple replaced
      WHOLESALE under the cache lock — a concurrent :meth:`gather`
      copies either the whole old row or the whole new row, never a
      half-applied one (the row array itself is never mutated after
      insertion).
    - ``ver`` is stamped from a cache-wide counter bumped per delta
      batch. A miss fetch snapshots :attr:`version` BEFORE its RPC and
      hands it back to :meth:`put`: an entry whose ``ver`` advanced
      past that snapshot was delta-upserted while the fetch was in
      flight, and the (older) fetched row is discarded — a stale PS
      read can never resurrect the pre-delta value.
    - :meth:`apply_delta` refreshes the TTL stamp atomically with the
      row (same tuple), so a delta-fresh row stays servable without
      any TTL round trip, and it never inserts or promotes — no
      eviction storms, no recency pollution from training bursts.

    Without a subscriber, ``ver`` stays 0 everywhere and behavior is
    exactly the PR-1 TTL cache: entries expire ``ttl_sec`` after their
    fetch, bounding staleness vs the training tier at one TTL. Absent
    signs cache as zero rows under the same TTL (the PS eval lookup's
    zero-fill), which also bounds how long a not-yet-admitted sign
    serves zeros.
    """

    def __init__(self, capacity: int, ttl_sec: float):
        self.capacity = int(capacity)
        self.ttl_sec = float(ttl_sec)
        self._od: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self._ver = 0
        self.delta_rows_applied = 0

    def __len__(self) -> int:
        return len(self._od)

    @property
    def version(self) -> int:
        """The delta-apply counter (atomic int read). Miss paths
        snapshot it BEFORE fetching so :meth:`put` can refuse to
        overwrite rows a delta refreshed mid-flight."""
        return self._ver

    def gather(self, signs: np.ndarray, dim: int,
               out: np.ndarray) -> np.ndarray:
        """Fill ``out`` rows for cached signs; return miss positions."""
        now = time.monotonic()
        miss: List[int] = []
        with self._lock:
            od = self._od
            for i, s in enumerate(signs):
                key = (dim, int(s))
                item = od.get(key)
                if item is None or item[1] < now:
                    miss.append(i)
                else:
                    out[i] = item[0]
                    od.move_to_end(key)
            self.hits += len(signs) - len(miss)
            self.misses += len(miss)
        return np.asarray(miss, np.int64)

    def put(self, signs: np.ndarray, dim: int, rows: np.ndarray,
            seen_ver: Optional[int] = None):
        """Install fetched rows. ``seen_ver`` (the :attr:`version`
        snapshot taken before the fetch RPC) guards the fetch-vs-delta
        race: any entry whose version advanced past the snapshot keeps
        its delta-applied row — the fetch read the PS before the delta
        landed and would roll the row back."""
        if self.capacity <= 0:
            return
        expires = time.monotonic() + self.ttl_sec
        stamp = self._ver if seen_ver is None else int(seen_ver)
        with self._lock:
            od = self._od
            for s, row in zip(signs, rows):
                key = (dim, int(s))
                if seen_ver is not None:
                    cur = od.get(key)
                    if cur is not None and cur[2] > seen_ver:
                        # a delta upsert landed while this fetch was in
                        # flight: the fetched row predates it
                        od.move_to_end(key)
                        continue
                od[key] = (np.array(row, np.float32), expires, stamp)
                od.move_to_end(key)
            while len(od) > self.capacity:
                od.popitem(last=False)

    def apply_delta(self, signs: np.ndarray, dim: int,
                    rows: np.ndarray) -> int:
        """Versioned in-place upsert of RESIDENT rows (the online
        subscriber's entry point): each resident (dim, sign) entry is
        replaced with a fresh ``(row, ttl-refreshed, new ver)`` tuple;
        non-resident signs are ignored (a later miss fetches the fresh
        row from the PS anyway). Never inserts, never evicts, never
        changes recency order — a training burst cannot churn the hot
        set. Returns rows applied."""
        if self.capacity <= 0:
            return 0
        expires = time.monotonic() + self.ttl_sec
        applied = 0
        with self._lock:
            self._ver += 1
            ver = self._ver
            od = self._od
            for s, row in zip(signs, rows):
                key = (dim, int(s))
                if key in od:
                    # assignment to an existing key keeps its LRU
                    # position; the tuple swap (not an in-place array
                    # write) is what makes concurrent gathers torn-free
                    od[key] = (np.array(row, np.float32), expires, ver)
                    applied += 1
            self.delta_rows_applied += applied
        return applied

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


# --- micro-batcher -------------------------------------------------------


class _PendingRequest:
    __slots__ = ("batch", "done", "pred", "error", "t_enqueue", "tctx",
                 "variant")

    def __init__(self, batch: PersiaBatch, variant: Optional[str] = None):
        self.batch = batch
        # multi-variant serving: merged forwards are single-variant
        # (the dense models differ), so the variant name joins the
        # coalescing group key. None = the default variant.
        self.variant = variant
        self.done = threading.Event()
        self.pred: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.t_enqueue = time.perf_counter()
        # the submitting handler thread's span context: the dispatcher
        # thread has none of its own, so the merged forward's span
        # parents to the first traced request it serves
        self.tctx = tracing.current_context()


class _MicroBatcher:
    """Coalesce concurrent predict requests into merged forwards.

    RPC handler threads park in :meth:`submit`; one dispatcher thread
    drains the queue, merges schema-compatible requests up to
    ``max_rows``, and runs the server's merged forward. The linger is
    adaptive: when the recent coalescing EWMA is ~1 (serial traffic)
    the dispatcher never sleeps, so an unloaded server serves at
    serialized-path latency; under concurrency the execution time of
    the previous merged forward naturally accumulates the next batch,
    and the EWMA unlocks a bounded ``max_wait`` linger for stragglers.
    """

    def __init__(self, run_merged, max_rows: int, max_wait_s: float):
        self._run_merged = run_merged
        self.max_rows = int(max_rows)
        self.max_wait_s = float(max_wait_s)
        self._queue: "deque[_PendingRequest]" = deque()
        self._cond = threading.Condition()
        self._running = True
        self._ewma = 1.0
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="infer-microbatcher")
        self._thread.start()

    def submit(self, batch: PersiaBatch, timeout: float = 120.0,
               variant: Optional[str] = None) -> np.ndarray:
        req = _PendingRequest(batch, variant)
        with self._cond:
            if not self._running:
                raise RpcError("inference server is shutting down")
            self._queue.append(req)
            self._cond.notify_all()
        if not req.done.wait(timeout):
            # shed the abandoned request: the client already got an
            # error, so leaving it queued would make an overloaded
            # dispatcher do extra lookup+forward work nobody reads
            with self._cond:
                try:
                    self._queue.remove(req)
                except ValueError:
                    pass  # already dispatched (in flight)
            raise RpcError("micro-batch dispatch timed out")
        if req.error is not None:
            raise req.error
        return req.pred

    def _pending_rows(self) -> int:
        return sum(r.batch.batch_size for r in self._queue)

    def _collect(self) -> List[_PendingRequest]:
        with self._cond:
            while self._running and not self._queue:
                self._cond.wait(0.25)
            if not self._queue:
                return []
            if self.max_wait_s > 0 and self._ewma > 1.05:
                deadline = time.monotonic() + self.max_wait_s
                while self._pending_rows() < self.max_rows:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
            if not self._queue:
                # the linger released the lock; a timed-out submit()
                # may have shed the last pending request meanwhile
                return []
            # group key = (schema signature, variant): different dense
            # models must never share one merged forward
            sig0 = (_batch_signature(self._queue[0].batch),
                    self._queue[0].variant)
            reqs: List[_PendingRequest] = []
            rows = 0
            while self._queue:
                r = self._queue[0]
                rb = r.batch.batch_size
                if reqs and (rows + rb > min(self.max_rows, MAX_BATCH_SIZE)
                             or (_batch_signature(r.batch),
                                 r.variant) != sig0):
                    break  # stays queued for the next dispatch
                reqs.append(self._queue.popleft())
                rows += rb
            self._ewma = 0.8 * self._ewma + 0.2 * len(reqs)
        return reqs

    def _loop(self):
        # the dispatcher must never die: a dead dispatcher bricks the
        # server (every predict parks in submit() until timeout), so
        # even a _collect bug only costs this iteration
        while True:
            try:
                reqs = self._collect()
            except Exception:
                _logger.exception("micro-batcher collect failed")
                time.sleep(0.05)  # never spin on a persistent bug
                reqs = []
            if not reqs:
                if not self._running:
                    return
                continue
            try:
                self._run_merged(reqs)
            except BaseException as e:  # fail whatever hasn't completed
                for r in reqs:
                    if not r.done.is_set():
                        r.error = e
                        r.done.set()

    def close(self):
        with self._cond:
            self._running = False
            self._cond.notify_all()
        self._thread.join(timeout=5.0)
        # fail anything still parked (submit after close raises upfront)
        with self._cond:
            while self._queue:
                r = self._queue.popleft()
                r.error = RpcError("inference server closed")
                r.done.set()


# --- the server ----------------------------------------------------------

_SERVER_SEQ = 0
_SERVER_SEQ_LOCK = threading.Lock()


def _model_zoo() -> dict:
    """Name -> model class map shared by main() and the variant_admin
    RPC's checkpoint-loading ``add``. Resolved lazily — the model
    classes pull in flax/jax, which an RPC-only importer of this
    module must not pay for."""
    from persia_tpu.models import DCNv2, DLRM, DNN, DeepFM, WideAndDeep

    return {"dnn": DNN, "dlrm": DLRM, "dcnv2": DCNv2, "deepfm": DeepFM,
            "wide_deep": WideAndDeep}


class _ServedVariant:
    """Data-plane state of one registered variant: its InferCtx (own
    jitted eval step + compiled-bucket set) and its isolated metric
    series. The registry (``persia_tpu.variants``) holds the routing
    truth; this holds what it takes to actually serve."""

    __slots__ = ("name", "ctx", "m_requests", "m_rows", "t_e2e",
                 "m_degraded", "m_zero_rows")

    def __init__(self, name: str, ctx, reg, base_labels: dict):
        self.name = name
        self.ctx = ctx
        labels = dict(base_labels, variant=name)
        self.m_requests = reg.counter(
            "inference_variant_requests_total", labels,
            help_text="predict requests served per model variant")
        self.m_rows = reg.counter(
            "inference_variant_rows_total", labels,
            help_text="prediction rows served per model variant")
        self.t_e2e = reg.histogram(
            "inference_variant_request_time_cost_sec", labels,
            help_text="end-to-end predict latency per model variant")
        self.m_degraded = reg.counter(
            "inference_variant_degraded_total", labels,
            help_text="predicts of this variant that served zero-vector "
                      "embedding fallback for some signs")
        self.m_zero_rows = reg.counter(
            "inference_variant_zero_rows_total", labels,
            help_text="embedding rows zero-filled for this variant's "
                      "predicts while the embedding tier was degraded")


class InferenceServer:
    """RPC predict server over an InferCtx.

    ``max_batch_rows=0`` (default) keeps the legacy serialized
    one-request-one-forward path; ``cache_rows=0`` (default) keeps the
    worker RPC on every lookup. Either can be enabled independently.
    ``worker=`` injects an in-process worker object (tests, single-node
    serving, bench) instead of dialing ``worker_addrs``.
    """

    def __init__(
        self,
        model,
        state,
        schema: EmbeddingSchema,
        worker_addrs: Optional[Sequence[str]] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        worker=None,
        max_batch_rows: int = 0,
        max_wait_us: int = 2000,
        buckets: Optional[Sequence[int]] = None,
        cache_rows: int = 0,
        cache_ttl_sec: float = 30.0,
        concurrent_streams: Optional[int] = None,
        http_port: Optional[int] = None,
        degraded_fallback: bool = True,
        variant_name: str = "default",
    ):
        # Opt-in contract: a default (serialized) server keeps the
        # legacy thread-per-connection RPC loop with NO shared-pool cap
        # on in-flight predicts; read-ahead streams only make sense when
        # the micro-batcher exists to coalesce them. Note the stream
        # pool also bounds how many requests can be parked in the
        # batcher at once (rpc.py sizes it at max(32, streams)), so
        # extreme coalescing targets should raise this too.
        if concurrent_streams is None:
            concurrent_streams = 32 if max_batch_rows > 0 else 1
        if worker is None:
            from persia_tpu.service.worker_service import \
                RemoteEmbeddingWorker
            from persia_tpu.service_discovery import \
                get_embedding_worker_services

            addrs = list(worker_addrs) if worker_addrs else \
                get_embedding_worker_services()
            worker = RemoteEmbeddingWorker(addrs)
            worker.schema = schema
        self.worker = worker
        self.schema = schema
        self.model = model
        self.ctx = InferCtx(model, state, schema, worker)
        # concurrent_streams lets ONE pipelined client connection keep
        # many predicts in flight (rpc.py read-ahead) — without it the
        # micro-batcher could only coalesce across connections
        self.server = RpcServer(host, port,
                                concurrent_streams=concurrent_streams)
        self.server.register("predict", self._predict)
        self.server.register("health", lambda p: b"ok")
        self.server.register("stats", self._stats)
        # multi-variant surface: plain methods on the request plane —
        # nothing rides the envelope, so a fleet that never registers a
        # second variant keeps a byte-identical wire (nobody calls
        # these; pinned via served-request counts in --mode online)
        self.server.register("predict_variant", self._predict_variant)
        self.server.register("variant_admin", self._variant_admin)

        self.max_batch_rows = min(int(max_batch_rows), MAX_BATCH_SIZE)
        if self.max_batch_rows > 0:
            self.buckets = tuple(sorted(
                buckets if buckets else default_buckets(self.max_batch_rows)))
            self._batcher: Optional[_MicroBatcher] = _MicroBatcher(
                self._run_merged, self.max_batch_rows, max_wait_us / 1e6)
        else:
            self.buckets = ()
            self._batcher = None
        self.cache = (HotRowCache(cache_rows, cache_ttl_sec)
                      if cache_rows > 0 else None)
        # Graceful degradation (default on): when the embedding tier is
        # unreachable for a lookup — circuit-open replica, shed
        # deadline, connection loss — predict serves ZERO VECTORS for
        # the affected signs instead of failing or stalling the whole
        # request. Signs served from the hot-row cache (and dims whose
        # fetch succeeded) keep their real embeddings; zero rows are
        # never cached, so recovery is immediate. Counted per port
        # below — a nonzero rate is the pager signal that the serving
        # tier is running on partial embeddings.
        self.degraded_fallback = bool(degraded_fallback)

        from persia_tpu.metrics import default_registry

        # the run label disambiguates a server RESTARTED on the same
        # port in the same process (fixed --port, tests): the registry
        # is process-wide and keyed by (name, labels), so without it a
        # fresh server would inherit — and blend into — the dead
        # server's counters
        global _SERVER_SEQ
        with _SERVER_SEQ_LOCK:
            _SERVER_SEQ += 1
            seq = _SERVER_SEQ
        labels = {"server": self.addr.rsplit(":", 1)[1], "run": str(seq)}
        reg = default_registry()
        self._m_requests = reg.counter("inference_requests_total", labels)
        self._m_batches = reg.counter("inference_batches_total", labels)
        self._m_rows = reg.counter("inference_rows_total", labels)
        self._m_padded = reg.counter("inference_padded_rows_total", labels)
        self._t_e2e = reg.histogram("inference_request_time_cost_sec",
                                    labels)
        self._t_queue = reg.histogram(
            "inference_queue_wait_time_cost_sec", labels)
        self._t_lookup = reg.histogram("inference_lookup_time_cost_sec",
                                       labels)
        self._t_forward = reg.histogram(
            "inference_forward_time_cost_sec", labels)
        # degradation observables (labels carry the server port)
        self._m_degraded = reg.counter("inference_degraded_lookups_total",
                                       labels)
        self._m_zero_rows = reg.counter(
            "inference_zero_fallback_rows_total", labels)
        # --- multi-variant layer (persia_tpu.variants): the boot model
        # is the first — and default — variant; plain `predict` serves
        # it through exactly the pre-variant path, so a server nobody
        # registers a second variant on behaves (and speaks) like the
        # single-model server it replaces.
        from persia_tpu.variants import VariantRegistry

        self._reg = reg
        self._metric_labels = labels
        self.variants = VariantRegistry()
        self.variants.add(variant_name, weight=1.0, default=True,
                          meta={"source": "boot"})
        # name -> _ServedVariant; mutated only under _variants_lock
        # (admin RPCs), read lock-free on the predict path (dict get is
        # atomic; a racing remove surfaces as a clean request error)
        self._variants_lock = threading.Lock()
        self._served_variants: Dict[str, _ServedVariant] = {
            variant_name: _ServedVariant(variant_name, self.ctx, reg,
                                         labels)}
        # request-field variant routing: when set, a plain predict
        # derives its A/B route key from this id feature's first sign
        # (frozen at server construction — per-request env reads have
        # no place on the predict hot path)
        self._route_feature = knobs.get("PERSIA_VARIANT_ROUTE_FEATURE")
        # online delta subscriber (persia_tpu.online), armed explicitly
        # via attach_delta_subscriber — None means the PR-13 TTL-only
        # freshness contract
        self.online = None
        # observability sidecar (see PsService): /metrics /healthz /trace
        from persia_tpu import obs_http

        self.http = obs_http.maybe_start(
            host, http_port, self._healthz,
            variants_fn=self._variants_doc)

    def _healthz(self) -> dict:
        doc = self.server.health()
        if self._batcher is not None:
            with self._batcher._cond:
                doc["microbatch_queue_depth"] = len(self._batcher._queue)
        if self.cache is not None:
            doc["cache_rows_resident"] = len(self.cache)
            doc["cache_hit_rate"] = round(self.cache.hit_rate, 4)
            # the serving tier's freshness BOUND: a cached row can lag
            # the PS (and the inc_update stream feeding it) by at most
            # this long — read it next to the infer-PS loader's
            # inc_update_last_delay_sec gauge for end-to-end
            # sign-to-servable age
            doc["cache_ttl_sec"] = self.cache.ttl_sec
        doc["requests_total"] = self._m_requests.value
        doc["degraded_lookups_total"] = self._m_degraded.value
        # online-learning freshness, PER SERVING REPLICA (the satellite
        # contract): the attached subscriber's stall clock + last
        # packet seq let serving_freshness_stale fire for THIS replica,
        # not just for a PS loader somewhere else in the fleet
        if self.online is not None:
            doc["online"] = self.online.health()
        # the variant topology rides every health doc (fleet.py's
        # /fleet/variants merges these across the serving tier)
        doc["variants"] = self._variants_doc()
        # elastic-tier observable: which routing epoch the embedding
        # fetch path splits by (an in-process EmbeddingWorker exposes
        # it; a RemoteEmbeddingWorker's replicas report their own)
        epoch = getattr(self.worker, "routing_epoch", None)
        if epoch is not None:
            doc["routing_epoch"] = epoch
        # the serving tier stays READY while degrading (zero-vector
        # fallback answers requests); degraded_lookups_total climbing is
        # the alert, not a routing decision
        doc["ready"] = True
        return doc

    @property
    def addr(self) -> str:
        return self.server.addr

    # --- variant control plane -------------------------------------------

    def add_variant(self, name: str, model=None, state=None,
                    weight: float = 0.0, default: bool = False,
                    meta: Optional[dict] = None):
        """Register a live variant: its own dense model/state (and
        jitted eval step), the SAME worker/cache/PS fleet. ``model``
        defaults to the boot model class instance (A/B of two dense
        checkpoints over one architecture, the common case)."""
        if state is None:
            raise ValueError("a variant needs its own dense state")
        ctx = InferCtx(model if model is not None else self.model,
                       state, self.schema, self.worker)
        with self._variants_lock:
            self.variants.add(name, weight=weight, default=default,
                              meta=meta)
            self._served_variants[name] = _ServedVariant(
                name, ctx, self._reg, self._metric_labels)
        _logger.info("variant %r registered (weight=%s default=%s)",
                     name, weight, default)

    def add_variant_from_checkpoint(self, name: str, model_name: str,
                                    dense_checkpoint: str,
                                    num_dense: int = 5,
                                    weight: float = 0.0,
                                    default: bool = False):
        """The operator-facing add: model zoo name + dense checkpoint
        path (what ``variant_admin`` / ``POST /variants`` carry)."""
        model = _model_zoo()[model_name]()
        state = load_dense_state(model, self.schema, num_dense,
                                 dense_checkpoint)
        self.add_variant(name, model=model, state=state, weight=weight,
                         default=default,
                         meta={"model": model_name,
                               "dense_checkpoint": dense_checkpoint})

    def remove_variant(self, name: str):
        with self._variants_lock:
            self.variants.remove(name)  # validates (default protected)
            self._served_variants.pop(name, None)
        _logger.info("variant %r removed", name)

    def promote_variant(self, name: str):
        """Make ``name`` the default (what plain ``predict`` serves) —
        the canary-promote / rollback primitive. The serving context
        must exist; the registry flips atomically, so in-flight
        requests finish on whichever variant they resolved."""
        if name not in self._served_variants:
            raise KeyError(f"variant {name!r} has no serving context")
        self.variants.promote(name)
        _logger.info("variant %r promoted to default", name)

    def _variants_doc(self) -> list:
        docs = self.variants.describe()
        for d in docs:
            sv = self._served_variants.get(d["name"])
            if sv is not None:
                d["requests"] = sv.m_requests.value
                d["rows"] = sv.m_rows.value
                d["degraded"] = sv.m_degraded.value
                d["compiled_buckets"] = sorted(
                    sv.ctx.eval_batch_rows_seen)
        return docs

    def _variant_admin(self, payload: bytes) -> bytes:
        """Live variant add/remove/promote/weight/drain — the RPC the
        k8s operator's ``POST /variants`` forwards to every serving
        replica (docs/DEPLOY.md runbook)."""
        req = msgpack.unpackb(payload, raw=False)
        op = req.get("op")
        if op == "list":
            return msgpack.packb({"variants": self._variants_doc()})
        name = req["name"]
        if op == "add":
            self.add_variant_from_checkpoint(
                name, req.get("model", "dnn"), req["dense_checkpoint"],
                num_dense=int(req.get("num_dense", 5)),
                weight=float(req.get("weight", 0.0)),
                default=bool(req.get("default", False)))
        elif op == "remove":
            self.remove_variant(name)
        elif op == "promote":
            self.promote_variant(name)
        elif op == "weight":
            self.variants.set_weight(name, float(req["weight"]))
        elif op == "drain":
            self.variants.set_status(name, "draining")
        elif op == "resume":
            self.variants.set_status(name, "live")
        else:
            raise RpcError(f"unknown variant_admin op {op!r}")
        return msgpack.packb({"ok": True,
                              "variants": self._variants_doc()})

    # --- online delta subscription ---------------------------------------

    def attach_delta_subscriber(self, inc_dir: str, **kw):
        """Close the online-learning loop: subscribe this server's
        hot-row cache to the trainer's incremental-update packet
        stream (persia_tpu.online.DeltaSubscriber). Routing awareness
        defaults to the in-process worker's live table when it has one
        (reshard epochs re-route the ownership filter automatically);
        a remote-worker server passes ``routing_fn`` explicitly or
        runs unfiltered."""
        from persia_tpu.online import DeltaSubscriber

        if self.cache is None:
            raise ValueError(
                "delta subscription upserts the hot-row cache; start "
                "the server with cache_rows > 0")
        if self.online is not None:
            raise RuntimeError("a delta subscriber is already attached")
        if "routing_fn" not in kw and hasattr(self.worker,
                                              "routing_window"):
            kw["routing_fn"] = lambda: self.worker.routing_window
        self.online = DeltaSubscriber(self.cache, inc_dir, **kw).start()
        _logger.info("delta subscriber attached to %s (scan=%.2fs)",
                     inc_dir, self.online.scan_interval_sec)
        return self.online

    # --- predict paths ---------------------------------------------------

    def _route_key_from_batch(self, batch: PersiaBatch) -> Optional[bytes]:
        """Field-based A/B routing (PERSIA_VARIANT_ROUTE_FEATURE): the
        named id feature's first sign is the request's route key — a
        user-id slot gives per-user-sticky variant assignment without
        any client change."""
        if self._route_feature is None or len(self.variants) <= 1:
            return None
        for f in batch.id_type_features:
            if f.name == self._route_feature and len(f.signs):
                return int(f.signs[0]).to_bytes(8, "little")
        return None

    def _predict(self, payload: bytes) -> bytes:
        # the legacy single-model wire: request = PersiaBatch bytes,
        # response = pack_arrays({}, [pred]) — BYTE-IDENTICAL to the
        # pre-variant server (no meta, no routing work) unless the
        # operator registers more variants / arms the route feature
        return self._serve(payload, None, None, reply_variant=False)

    def _predict_variant(self, payload: bytes) -> bytes:
        """Variant-addressed predict: msgpack ``{v: explicit variant |
        None, k: route key bytes | None, b: PersiaBatch bytes}``; the
        response meta names the variant that served."""
        req = msgpack.unpackb(payload, raw=False)
        return self._serve(req["b"], req.get("v"), req.get("k"),
                           reply_variant=True)

    def _serve(self, payload: bytes, explicit: Optional[str],
               key: Optional[bytes], reply_variant: bool) -> bytes:
        t0 = time.perf_counter()
        with tracing.span("serving/predict"):
            batch = PersiaBatch.from_bytes(payload)
            if explicit is None and key is None:
                key = self._route_key_from_batch(batch)
            try:
                vname = self.variants.route(key=key, explicit=explicit)
            except KeyError as e:
                raise RpcError(str(e))
            sv = self._served_variants.get(vname)
            if sv is None:
                raise RpcError(
                    f"variant {vname!r} has no serving context")
            self._m_requests.inc()
            sv.m_requests.inc()
            if self._batcher is not None:
                pred = self._batcher.submit(batch, variant=vname)
            else:
                pred = self._forward(batch, sv)
                self._m_batches.inc()
                self._m_rows.inc(batch.batch_size)
                sv.m_rows.inc(batch.batch_size)
        dt = time.perf_counter() - t0
        self._t_e2e.observe(dt)
        sv.t_e2e.observe(dt)
        meta = {"variant": vname} if reply_variant else {}
        return pack_arrays(meta, [np.ascontiguousarray(pred)])

    def _bucket_for(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        return rows  # oversized request: exact shape, no padding

    def _run_merged(self, reqs: List[_PendingRequest]):
        """Dispatcher entry: merge -> pad to bucket -> one lookup + one
        jitted forward -> scatter per-request row slices. The collect
        loop groups by (signature, variant), so a merged batch is
        single-variant by construction."""
        now = time.perf_counter()
        for r in reqs:
            self._t_queue.observe(now - r.t_enqueue)
        sv = None
        if reqs[0].variant is not None:
            sv = self._served_variants.get(reqs[0].variant)
            if sv is None:
                raise RpcError(
                    f"variant {reqs[0].variant!r} removed mid-flight")
        tctx = next((r.tctx for r in reqs if r.tctx is not None), None)
        kw = {"ctx": tctx} if tctx is not None else {}
        with tracing.span("serving/merged_forward", n_reqs=len(reqs), **kw):
            merged, sizes = merge_batches([r.batch for r in reqs])
            rows = merged.batch_size
            bucket = self._bucket_for(rows)
            padded = pad_batch(merged, bucket)
            pred = self._forward(padded, sv)
        self._m_batches.inc()
        self._m_rows.inc(rows)
        self._m_padded.inc(bucket - rows)
        if sv is not None:
            sv.m_rows.inc(rows)
        off = 0
        for r, s in zip(reqs, sizes):
            r.pred = pred[off:off + s]
            off += s
            r.done.set()

    def _forward(self, batch: PersiaBatch,
                 sv: Optional[_ServedVariant] = None) -> np.ndarray:
        if sv is None:
            sv = self._served_variants[self.variants.default]
        with self._t_lookup.timer(), tracing.span("serving/lookup"):
            lookup = self._lookup(batch.id_type_features, sv)
        with self._t_forward.timer(), tracing.span("serving/forward"):
            pred, _labels = sv.ctx.forward_prepared(batch, lookup)
            return np.asarray(pred)

    # --- cached lookup path ----------------------------------------------

    def _lookup(self, id_type_features: List[IDTypeFeature],
                sv: Optional[_ServedVariant] = None):
        if self.cache is None:
            try:
                return self.worker.lookup_direct(id_type_features,
                                                 training=False)
            except DEGRADABLE_ERRORS as e:
                if not self.degraded_fallback:
                    raise
                return self._zero_lookup(id_type_features, e, sv)
        return self._lookup_cached(id_type_features, sv)

    def _count_degraded(self, sv: Optional[_ServedVariant], rows: int):
        self._m_degraded.inc()
        self._m_zero_rows.inc(rows)
        if sv is not None:
            # per-variant isolation: degraded service is attributed to
            # the variant whose predict paid it (the by-variant SLO
            # rule reads these)
            sv.m_degraded.inc()
            sv.m_zero_rows.inc(rows)

    def _zero_lookup(self, id_type_features: List[IDTypeFeature], cause,
                     sv: Optional[_ServedVariant] = None):
        """Whole-lookup degradation (no cache to salvage hits from):
        preprocess locally — the same transforms the worker would run,
        so shapes are identical — and zero-fill every embedding row.
        The model still answers (dense features carry what they carry);
        a recommendation served on partial signal beats a 500."""
        from persia_tpu.worker import middleware as mw

        feats = mw.preprocess_batch(id_type_features, self.schema)
        out = {}
        rows = 0
        for f in feats:
            slot = self.schema.get_slot(f.name)
            mat = np.zeros((f.num_distinct, slot.dim), np.float32)
            rows += f.num_distinct
            out[f.name] = mw.postprocess_feature(f, slot, mat)
        self._count_degraded(sv, rows)
        _logger.warning("degraded predict: embedding tier unreachable "
                        "(%s); %d rows served as zero vectors", cause,
                        rows)
        return out

    def _lookup_cached(self, id_type_features: List[IDTypeFeature],
                       sv: Optional[_ServedVariant] = None):
        """Preprocess locally (the same dedup/hashstack/prefix transforms
        the worker runs, so cache keys are post-transform signs — the
        exact PS keyspace inc_update writes), serve distinct signs from
        the LRU, and fetch only the misses through ONE deduplicated
        ``lookup_signs`` RPC per dim. Because requests were merged
        before this runs, the dedup is cross-request for free."""
        from persia_tpu.worker import middleware as mw

        feats = mw.preprocess_batch(id_type_features, self.schema)
        # version snapshot BEFORE any miss fetch: a delta upsert landing
        # while the RPC is in flight advances the cache version past
        # this, and put() then refuses to roll the row back to the
        # older PS read (the stale-slot resurrection guard)
        seen_ver = self.cache.version
        mats: List[np.ndarray] = []
        misses: Dict[int, list] = {}
        for f in feats:
            dim = self.schema.get_slot(f.name).dim
            mat = np.zeros((f.num_distinct, dim), np.float32)
            miss_pos = self.cache.gather(f.distinct_signs, dim, mat)
            if len(miss_pos):
                misses.setdefault(dim, []).append(
                    (mat, miss_pos, f.distinct_signs[miss_pos]))
            mats.append(mat)
        for dim, parts in misses.items():
            all_signs = np.concatenate([p[2] for p in parts])
            uniq, inverse = np.unique(all_signs, return_inverse=True)
            try:
                rows = self.worker.lookup_signs(uniq, dim)
            except DEGRADABLE_ERRORS as e:
                if not self.degraded_fallback:
                    raise
                # the miss rows stay at their zero initialization; the
                # CACHED signs of this request (and every other dim)
                # keep their real embeddings — only the unreachable
                # replica's share degrades. Zero rows are NOT cached,
                # so the first post-recovery request refetches.
                self._count_degraded(sv, len(all_signs))
                _logger.warning(
                    "degraded lookup (dim=%d): %d miss rows served as "
                    "zero vectors (%s)", dim, len(all_signs), e)
                continue
            self.cache.put(uniq, dim, rows, seen_ver=seen_ver)
            pos = 0
            for mat, miss_pos, s in parts:
                mat[miss_pos] = rows[inverse[pos:pos + len(s)]]
                pos += len(s)
        out = {}
        for f, mat in zip(feats, mats):
            out[f.name] = mw.postprocess_feature(
                f, self.schema.get_slot(f.name), mat)
        return out

    # --- observability ---------------------------------------------------

    def _stats(self, payload: bytes) -> bytes:
        req = self._m_requests.value
        bat = self._m_batches.value
        rows = self._m_rows.value
        padded = self._m_padded.value
        d = {
            "requests": req,
            "batches": bat,
            "rows": rows,
            "padded_rows": padded,
            "avg_coalesce": req / bat if bat else 0.0,
            "batch_fill_ratio": rows / (rows + padded) if rows else 0.0,
            "queue_wait_p50_ms": self._t_queue.percentile(50) * 1e3,
            "queue_wait_p99_ms": self._t_queue.percentile(99) * 1e3,
            "request_p50_ms": self._t_e2e.percentile(50) * 1e3,
            "request_p99_ms": self._t_e2e.percentile(99) * 1e3,
            "compiled_buckets": sorted(self.ctx.eval_batch_rows_seen),
            "buckets": list(self.buckets),
        }
        d["degraded_lookups"] = self._m_degraded.value
        d["zero_fallback_rows"] = self._m_zero_rows.value
        if self.cache is not None:
            d.update(cache_hit_rate=self.cache.hit_rate,
                     cache_hits=self.cache.hits,
                     cache_misses=self.cache.misses,
                     cache_rows_resident=len(self.cache),
                     cache_delta_rows_applied=(
                         self.cache.delta_rows_applied))
        if len(self.variants) > 1:
            d["variants"] = self._variants_doc()
        if self.online is not None:
            d["online"] = self.online.health()
        return msgpack.packb(d)

    # --- lifecycle -------------------------------------------------------

    def serve_background(self):
        self.server.serve_background()

    def serve_forever(self):
        _logger.info(
            "inference server listening on %s (max_batch_rows=%d "
            "buckets=%s cache_rows=%s)", self.addr, self.max_batch_rows,
            list(self.buckets),
            # `is not None`, not truthiness: an EMPTY cache is falsy
            # through __len__
            self.cache.capacity if self.cache is not None else 0)
        self.server.serve_forever()

    def stop(self):
        self.server.stop()
        if self._batcher is not None:
            self._batcher.close()
        if self.online is not None:
            self.online.stop()
        if self.http is not None:
            self.http.stop()


class InferenceClient:
    def __init__(self, addr: str):
        self.client = RpcClient(addr)

    def predict(self, batch: PersiaBatch) -> np.ndarray:
        return self.predict_bytes(batch.to_bytes())

    def predict_bytes(self, payload: bytes) -> np.ndarray:
        _, (pred,) = unpack_arrays(self.client.call("predict", payload))
        return pred

    def predict_variant(self, batch, variant: Optional[str] = None,
                        key: Optional[bytes] = None):
        """Variant-addressed predict: pin a variant explicitly, or hand
        a route key to the server's deterministic weighted split.
        Returns ``(pred, served_variant_name)``."""
        payload = batch if isinstance(batch, (bytes, bytearray)) \
            else batch.to_bytes()
        req = msgpack.packb(
            {"v": variant, "k": bytes(key) if key is not None else None,
             "b": bytes(payload)}, use_bin_type=True)
        meta, (pred,) = unpack_arrays(
            self.client.call("predict_variant", req))
        return pred, meta.get("variant")

    def variant_admin(self, op: str, **kw) -> dict:
        """Live variant control: ``op`` in add | remove | promote |
        weight | drain | resume | list (see InferenceServer
        ``_variant_admin``)."""
        return self.client.call_msg("variant_admin", op=op, **kw)

    def predict_many(self, batches: Sequence) -> List[np.ndarray]:
        """Pipelined predicts on one connection (rpc.py ``call_many``):
        with the server's read-ahead streams, a single client can keep
        the micro-batcher full without threads."""
        payloads = [b if isinstance(b, (bytes, bytearray)) else b.to_bytes()
                    for b in batches]
        return [unpack_arrays(r)[1][0]
                for r in self.client.call_many("predict", payloads)]

    def stats(self) -> dict:
        return msgpack.unpackb(self.client.call("stats"), raw=False)

    def healthy(self) -> bool:
        try:
            return self.client.call("health") == b"ok"
        except Exception:
            return False


def build_state_template(model, schema: EmbeddingSchema,
                         num_dense: int, seed: int = 0):
    """A TrainState with the right structure for deserializing a dense
    checkpoint (flax.serialization.from_bytes needs a target pytree):
    synthesizes one batch worth of zero inputs from the schema shapes."""
    import jax
    import jax.numpy as jnp

    from persia_tpu.parallel.train import create_train_state

    non_id = [jnp.zeros((1, num_dense), jnp.float32)]
    emb_inputs = []
    for name in schema.feature_names:
        slot = schema.get_slot(name)
        if slot.embedding_summation:
            emb_inputs.append(jnp.zeros((1, slot.dim), jnp.float32))
        else:
            cap = slot.sample_fixed_size + 1
            emb_inputs.append((
                jnp.zeros((cap, slot.dim), jnp.float32),
                jnp.zeros((1, slot.sample_fixed_size), jnp.int32),
            ))
    import optax

    return create_train_state(model, optax.sgd(0.0), jax.random.key(seed),
                              non_id, emb_inputs)


def load_dense_state(model, schema: EmbeddingSchema, num_dense: int,
                     path: str):
    """Dense checkpoint bytes (checkpoint.DENSE_FILE) -> TrainState.

    Serving never touches optimizer state, and the training optimizer is
    unknown here (the checkpoint may hold adam/adagrad/... pytrees), so
    only params/batch_stats/step are restored against the template —
    the opt_state subtree of the checkpoint is ignored."""
    import jax.numpy as jnp
    from flax import serialization

    template = build_state_template(model, schema, num_dense)
    with open(path, "rb") as f:
        raw = serialization.msgpack_restore(f.read())
    params = serialization.from_state_dict(template.params, raw["params"])
    batch_stats = serialization.from_state_dict(
        template.batch_stats, raw.get("batch_stats", {}))
    step = raw.get("step", 0)
    return template.replace(params=params, batch_stats=batch_stats,
                            step=jnp.asarray(step, jnp.int32))


def main(argv=None):
    """Serve a trained model (reference: the torchserve handler wiring,
    examples/src/adult-income/launch_ts.sh + serve_handler.py)."""
    import argparse
    import os

    from persia_tpu.utils import enable_compile_cache

    enable_compile_cache()

    zoo = _model_zoo()
    p = argparse.ArgumentParser(prog="persia-tpu-serving")
    p.add_argument("--model", choices=sorted(zoo), default="dnn")
    p.add_argument("--dense-checkpoint", required=True,
                   help="dense.msgpack from dump_checkpoint")
    p.add_argument("--embedding-config", required=True)
    p.add_argument("--num-dense", type=int, default=5,
                   help="dense feature width the model was trained with")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8501)
    p.add_argument("--worker-addrs", default=None,
                   help="comma-separated; default EMBEDDING_WORKER_SERVICE")
    p.add_argument("--coordinator",
                   default=knobs.get_raw("PERSIA_COORDINATOR_ADDR"),
                   help="register this serving replica (and its "
                        "observability sidecar) with the coordinator so "
                        "the fleet monitor scrapes it")
    p.add_argument("--replica-index", type=int,
                   default=int(os.environ.get("REPLICA_INDEX", 0)))
    p.add_argument("--max-batch-rows", type=int, default=0,
                   help="enable micro-batching up to this many coalesced "
                        "rows (0 = serialized legacy path)")
    p.add_argument("--max-wait-us", type=int, default=2000,
                   help="adaptive linger window for straggler coalescing")
    p.add_argument("--cache-rows", type=int, default=0,
                   help="hot-row LRU capacity (0 = no cache)")
    p.add_argument("--cache-ttl-sec", type=float, default=30.0,
                   help="hot-row TTL; bounds staleness vs inc_update")
    p.add_argument("--inc-dir", default=None,
                   help="attach the online delta subscriber to this "
                        "incremental-update packet directory (the "
                        "trainer PS tier's inc_dir): trained rows "
                        "upsert the hot-row cache in place instead of "
                        "waiting out the TTL. Requires --cache-rows")
    p.add_argument("--online-scan-sec", type=float, default=None,
                   help="delta-subscriber scan interval "
                        "(default PERSIA_ONLINE_SCAN_SEC)")
    p.add_argument("--variant", action="append", default=[],
                   metavar="NAME=WEIGHT:MODEL:DENSE_CKPT[:default]",
                   help="register an extra serving variant at boot "
                        "(repeatable); more can be added live via the "
                        "variant_admin RPC / the operator's "
                        "POST /variants")
    p.add_argument("--variant-name", default="default",
                   help="name of the boot model's variant (the default "
                        "unless a --variant entry claims it)")
    p.add_argument("--no-degraded-fallback", action="store_true",
                   help="fail predicts when the embedding tier is "
                        "unreachable instead of serving zero-vector "
                        "embeddings for the affected signs")
    p.add_argument("--wire-codec", default=None,
                   choices=["off", "fp16", "fp16+int8"],
                   help="embedding-row wire precision policy "
                        "(PERSIA_PS_WIRE_CODEC): the serving tier's "
                        "miss-fetch hop ships fp16 rows when enabled; "
                        "legacy peers negotiate down to fp32")
    from persia_tpu import obs_http

    obs_http.add_http_args(p)
    args = p.parse_args(argv)
    tracing.set_service_name(f"serving:{args.port}")
    if args.wire_codec is not None:
        # the policy env is read by every row-wire client built below
        # (RemoteEmbeddingWorker's miss-fetch hop, and through the
        # worker tier, the PS lookup wire)
        os.environ["PERSIA_PS_WIRE_CODEC"] = args.wire_codec

    schema = EmbeddingSchema.load(args.embedding_config)
    model = zoo[args.model]()
    state = load_dense_state(model, schema, args.num_dense,
                             args.dense_checkpoint)
    addrs = None
    if args.worker_addrs:
        addrs = [a.strip() for a in args.worker_addrs.split(",")
                 if a.strip()]
    server = InferenceServer(model, state, schema, worker_addrs=addrs,
                             host=args.host, port=args.port,
                             max_batch_rows=args.max_batch_rows,
                             max_wait_us=args.max_wait_us,
                             cache_rows=args.cache_rows,
                             cache_ttl_sec=args.cache_ttl_sec,
                             http_port=obs_http.port_from_args(args),
                             degraded_fallback=not args.no_degraded_fallback,
                             variant_name=args.variant_name)
    for spec in args.variant:
        # NAME=WEIGHT:MODEL:DENSE_CKPT[:default]
        name, _, rest = spec.partition("=")
        parts = rest.split(":")
        if len(parts) < 3:
            p.error(f"--variant {spec!r}: expected "
                    "NAME=WEIGHT:MODEL:DENSE_CKPT[:default]")
        server.add_variant_from_checkpoint(
            name, parts[1], parts[2], num_dense=args.num_dense,
            weight=float(parts[0]),
            default=len(parts) > 3 and parts[3] == "default")
    if args.inc_dir:
        kw = {}
        if args.online_scan_sec is not None:
            kw["scan_interval_sec"] = args.online_scan_sec
        server.attach_delta_subscriber(args.inc_dir, **kw)
    obs_http.write_addr_file_from_args(server.http, args)
    if args.coordinator:
        from persia_tpu.service.coordinator import (
            ROLE_INFERENCE,
            CoordinatorClient,
        )

        CoordinatorClient(args.coordinator).register(
            ROLE_INFERENCE, args.replica_index, server.addr,
            http_addr=server.http.addr if server.http else None)
    server.serve_forever()


if __name__ == "__main__":
    main()
