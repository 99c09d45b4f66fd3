"""Embedding-worker service + the remote worker client.

Service binary for one embedding-worker replica (reference:
src/bin/persia-embedding-worker.rs + the RPC surface of
embedding_worker_service/mod.rs:1372-1561). Hosts an
:class:`~persia_tpu.worker.worker.EmbeddingWorker` whose PS clients are
:class:`~persia_tpu.service.ps_service.PsClient` RPC stubs discovered
through the coordinator (with replica-count wait + backoff, mirroring
AllEmbeddingServerClient, mod.rs:139-339).

``RemoteEmbeddingWorker`` is the trainer/data-loader side: it exposes the
same interface as the in-process EmbeddingWorker, with composite
``(worker_addr, ref_id)`` handles so a fleet of worker replicas behaves
like one object (round-robin ingestion like the reference's data-loader
publisher, persia-core/src/nats.rs:250-312).

Run: ``python -m persia_tpu.service.worker_service --replica-index 0
--replica-size 1 --coordinator ... --embedding-config schema.yml``
"""

import argparse
import itertools
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

import msgpack
import numpy as np

from persia_tpu import knobs
from persia_tpu.config import EmbeddingSchema, GlobalConfig
from persia_tpu.logger import get_default_logger
from persia_tpu.rpc import RpcClient, RpcServer
from persia_tpu.service import serialization as ser
from persia_tpu.service.coordinator import (
    ROLE_PS,
    ROLE_WORKER,
    CoordinatorClient,
)
from persia_tpu.service.ps_service import PsClient
from persia_tpu.worker import mw_native
from persia_tpu.worker.worker import EmbeddingWorker, ForwardBufferFull

_logger = get_default_logger(__name__)


class WorkerService:
    def __init__(self, worker: EmbeddingWorker, host: str = "127.0.0.1",
                 port: int = 0, concurrent_streams: int = 8,
                 http_port: Optional[int] = None):
        self.worker = worker
        # dispatch pool: a pipelining trainer/data-loader connection
        # (tagged framing) gets out-of-order completion, so one slow
        # lookup fan-out does not convoy the next batch's ingestion
        self.server = RpcServer(host, port,
                                concurrent_streams=concurrent_streams)
        # observability sidecar (see PsService): /metrics /healthz /trace
        from persia_tpu import obs_http

        # readiness is an RPC fan-out to every PS replica — cache it so
        # aggressive probe intervals don't multiply PS control traffic.
        # Initialized BEFORE the sidecar starts serving: a probe landing
        # in the construction window must not 500 on missing state.
        self._ready_lock = threading.Lock()
        self._ready_cache = (0.0, True)
        # gradient shipments per trainer process label ("" = unlabeled
        # single-process trainer) — the fleet's per-process data-plane view
        self._ship_lock = threading.Lock()
        self._ship_counts: Dict[str, int] = {}
        self.http = obs_http.maybe_start(host, http_port, self._health)
        s = self.server
        s.register("forward_batched", self._forward_batched)
        s.register("forward_batch_id", self._forward_batch_id)
        s.register("forward_batched_direct", self._forward_batched_direct)
        s.register("lookup_signs", self._lookup_signs)
        s.register("update_gradients", self._update_gradients)
        s.register("configure", self._configure)
        s.register("register_optimizer", self._register_optimizer)
        s.register("dump", self._dump)
        s.register("load", self._load)
        s.register("staleness", self._staleness)
        s.register("ready", self._ready)
        # elastic-tier control plane: the reshard controller pushes the
        # successor routing table at cutover; scale-out additionally
        # names the PS addresses the grown fleet serves from
        s.register("apply_routing", self._apply_routing)
        s.register("close_routing_window", self._close_routing_window)

    @property
    def addr(self):
        return self.server.addr

    def stop(self):
        self.server.stop()
        if self.http is not None:
            self.http.stop()

    def _health(self) -> dict:
        """Live middleware internals for /healthz: the buffer depths and
        staleness are THE signals for a stuck hybrid pipeline (permits
        all held = staleness pegged; loaders outrunning trainers =
        forward buffer climbing toward ForwardBufferFull)."""
        doc = self.server.health()
        w = self.worker
        with w._lock:
            doc["forward_buffer_depth"] = len(w._forward_id_buffer)
            doc["post_forward_buffer_depth"] = len(w._post_forward_buffer)
            doc["staleness"] = w.staleness
        doc["ps_replicas"] = w.replica_size
        # which middleware kernels this worker runs (the numpy twins are
        # a silent fallback when the native library is absent or stale)
        doc["mw_kernels"] = "native" if mw_native.available() else "numpy"
        # elastic-tier observable: which routing epoch this worker
        # splits by (the fleet's /fleet/routing skew check reads it)
        doc["routing_epoch"] = w.routing_epoch
        # readiness: can this worker actually serve lookups right now
        # (every PS replica armed and Idle)? /healthz?ready=1 turns a
        # False into a 503 so probes stop routing here mid-PS-recovery
        doc["ready"] = self._ready_cached()
        with self._ship_lock:
            if self._ship_counts:
                doc["ship_counts"] = dict(self._ship_counts)
        return doc

    READY_CACHE_SEC = 2.0

    def _ready_cached(self) -> bool:
        now = time.monotonic()
        with self._ready_lock:
            t, val = self._ready_cache
            if now - t < self.READY_CACHE_SEC:
                return val
        try:
            ready = all(
                c.ready_for_serving() for c in self.worker.ps_clients
                if hasattr(c, "ready_for_serving")
            )
        except Exception:
            ready = False
        with self._ready_lock:
            self._ready_cache = (time.monotonic(), ready)
        return ready

    def _forward_batched(self, payload: bytes) -> bytes:
        _, feats = ser.unpack_id_features(payload)
        ref_id = self.worker.put_batch(feats)  # raises ForwardBufferFull
        return msgpack.packb({"ref_id": ref_id})

    def _forward_batch_id(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        result = self.worker.lookup(req["ref_id"], training=req["training"])
        return ser.pack_lookup_result(result)

    def _forward_batched_direct(self, payload: bytes) -> bytes:
        meta, feats = ser.unpack_id_features(payload)
        result = self.worker.lookup_direct(feats,
                                           training=meta.get("training", False))
        return ser.pack_lookup_result(result)

    def _lookup_signs(self, payload: bytes) -> bytes:
        """Dedup'd eval row lookup — the inference hot-row cache's miss
        fetch (read-only: absent signs zero-fill, nothing is created).
        A client may ask for fp16 rows (``resp`` meta key): the response
        meta names the encoding, so legacy peers on either side keep the
        fp32 wire (same self-describing rule as the PS lookup codec)."""
        from persia_tpu.rpc import pack_arrays_sg, unpack_arrays

        meta, (signs,) = unpack_arrays(payload)
        rows = self.worker.lookup_signs(signs, meta["dim"])
        if meta.get("resp") == "fp16" and self.server._enable_codec:
            # _enable_codec keeps legacy-peer emulation honest (see
            # PsService._lookup)
            from persia_tpu import wire_codec

            return pack_arrays_sg({"codec": "fp16"},
                                  [wire_codec.encode_fp16_rows(rows)])
        return pack_arrays_sg({}, [rows])

    def _update_gradients(self, payload: bytes) -> bytes:
        meta, grads = ser.unpack_gradients(payload)
        self.worker.update_gradients(meta["ref_id"], grads,
                                     loss_scale=meta.get("loss_scale", 1.0))
        # multi-process trainers label their shipments (meta["process"])
        # so the fleet can see every group member's backward traffic
        # landing; single-process trainers send no label (byte-identical
        # wire) and are counted under ""
        label = str(meta.get("process", ""))
        with self._ship_lock:
            self._ship_counts[label] = self._ship_counts.get(label, 0) + 1
        return b""

    def _configure(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self.worker.configure_parameter_servers(
            req["init_method"], req["init_params"], req["admit_probability"],
            req["weight_bound"], req["enable_weight_bound"],
        )
        return b""

    def _register_optimizer(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self.worker.register_optimizer(req["config"])
        return b""

    def _dump(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self.worker.dump(req["path"])
        return b""

    def _load(self, payload: bytes) -> bytes:
        req = msgpack.unpackb(payload, raw=False)
        self.worker.load(req["path"])
        return b""

    def _staleness(self, payload: bytes) -> bytes:
        return msgpack.packb({"staleness": self.worker.staleness})

    def _apply_routing(self, payload: bytes) -> bytes:
        from persia_tpu.routing import RoutingTable

        req = msgpack.unpackb(payload, raw=False)
        table = RoutingTable.from_bytes(req["table"])
        clients = None
        if req.get("ps_addrs"):
            # reuse the live client (and its pooled connections) for
            # every address we already hold; dial only the newcomers —
            # apply_routing closes whichever clients drop out
            held = {getattr(c, "addr", None): c
                    for c in self.worker.ps_clients}
            clients = [held.get(a) or PsClient(a)
                       for a in req["ps_addrs"]]
        applied = self.worker.apply_routing(table, ps_clients=clients)
        return msgpack.packb({"applied": bool(applied),
                              "epoch": self.worker.routing_epoch})

    def _close_routing_window(self, payload: bytes) -> bytes:
        self.worker.close_routing_window()
        return b""

    def _ready(self, payload: bytes) -> bytes:
        """Ready iff every PS replica is serving (the trainer's recovery
        wait polls this; reference forward.rs:708-715 wait_for_serving)."""
        try:
            ready = all(
                c.ready_for_serving() for c in self.worker.ps_clients
                if hasattr(c, "ready_for_serving")
            )
        except Exception:
            ready = False
        return msgpack.packb({"ready": bool(ready)})


class PartialPublishError(RuntimeError):
    """A routing-table broadcast reached only part of a worker fleet.
    ``applied_any`` is the controller's rollback gate: True means at
    least one replica already routes by the new epoch, so donors must
    STAY frozen (retry the publish) rather than roll back."""

    def __init__(self, applied_any: bool, failures):
        self.applied_any = bool(applied_any)
        self.failures = list(failures)
        super().__init__(
            f"routing publish failed on {len(self.failures)} worker "
            f"replica(s) (applied_any={self.applied_any}): "
            + "; ".join(f"{a}: {e!r}" for a, e in self.failures))


class RemoteEmbeddingWorker:
    """Client fan-in over one or more worker replicas, presenting the
    in-process EmbeddingWorker interface with (addr, id) composite refs."""

    def __init__(self, addrs: Sequence[str]):
        if not addrs:
            raise ValueError("need at least one embedding-worker address")
        self.addrs = list(addrs)
        self._clients = {a: RpcClient(a) for a in self.addrs}
        self._rr = itertools.cycle(self.addrs)
        self._rr_lock = threading.Lock()
        # multi-process trainers set this (e.g. "p1") so their backward
        # shipments are attributable per group member; None (default)
        # sends the historic meta dict — byte-identical wire
        self.process_label: Optional[str] = None
        self.schema = None  # populated lazily for prepare_features parity
        # the serving tier's miss-fetch hop honors the same wire-codec
        # policy as the PS hop: fp16 rows when PERSIA_PS_WIRE_CODEC
        # includes fp16 (self-describing response meta, so any old/new
        # peer pairing still speaks fp32). Same STRICT parse as
        # PsClient — a typo'd policy fails loudly, never silently fp32.
        self._fp16_rows = PsClient.parse_wire_codec(
            knobs.get("PERSIA_PS_WIRE_CODEC"))[0]

    def _next_addr(self) -> str:
        with self._rr_lock:
            return next(self._rr)

    def _client_for(self, ref) -> RpcClient:
        return self._clients[ref[0]]

    # --- data-loader / trainer interface --------------------------------

    def put_batch(self, id_type_features) -> tuple:
        addr = self._next_addr()
        # non-idempotent: dedup id prevents a retry from leaving an
        # orphaned forward-buffer entry on the worker
        resp = self._clients[addr].call(
            "forward_batched", ser.pack_id_features(id_type_features),
            dedup=True)
        return (addr, msgpack.unpackb(resp, raw=False)["ref_id"])

    def lookup(self, ref, training: bool = True) -> Dict[str, object]:
        client = self._client_for(ref)
        payload = msgpack.packb({"ref_id": ref[1], "training": training},
                                use_bin_type=True)
        # non-idempotent: lookup pops the forward buffer and (training)
        # bumps staleness; the dedup id keeps a blind retry from
        # double-counting staleness or 404ing on the popped ref_id
        return ser.unpack_lookup_result(
            client.call("forward_batch_id", payload, dedup=True))

    def lookup_direct(self, id_type_features, training: bool = False):
        addr = self._next_addr()
        payload = ser.pack_id_features(id_type_features,
                                       {"training": training})
        return ser.unpack_lookup_result(
            self._clients[addr].call("forward_batched_direct", payload))

    def lookup_signs(self, signs: np.ndarray, dim: int) -> np.ndarray:
        """Serving-tier miss fetch (see EmbeddingWorker.lookup_signs):
        idempotent read, so no dedup id; round-robin across replicas.
        Rows travel fp16 when the wire-codec policy asks for it (decode
        keys on the response meta — legacy workers answer fp32)."""
        from persia_tpu.rpc import pack_arrays, unpack_arrays

        addr = self._next_addr()
        meta = {"dim": int(dim)}
        if self._fp16_rows:
            meta["resp"] = "fp16"
        resp = self._clients[addr].call(
            "lookup_signs",
            pack_arrays(meta, [np.ascontiguousarray(signs, np.uint64)]))
        rmeta, (rows,) = unpack_arrays(resp)
        if rmeta.get("codec") == "fp16":
            from persia_tpu import wire_codec

            rows = wire_codec.decode_fp16_rows(rows)
        return rows

    def lookup_direct_training(self, id_type_features):
        ref = self.put_batch(id_type_features)
        return ref, self.lookup(ref, training=True)

    def update_gradients(self, ref, grads: Dict[str, np.ndarray],
                         loss_scale: float = 1.0):
        client = self._client_for(ref)
        meta = {"ref_id": ref[1], "loss_scale": loss_scale}
        if self.process_label is not None:
            meta["process"] = self.process_label
        # non-idempotent: dedup id makes the retry at-most-once server-side
        client.call("update_gradients", ser.pack_gradients(grads, meta),
                    dedup=True)

    # --- control plane ---------------------------------------------------

    def configure_parameter_servers(self, init_method, init_params,
                                    admit_probability, weight_bound,
                                    enable_weight_bound=True):
        for c in self._clients.values():
            c.call_msg(
                "configure", init_method=init_method, init_params=init_params,
                admit_probability=admit_probability, weight_bound=weight_bound,
                enable_weight_bound=enable_weight_bound,
            )

    def register_optimizer(self, config: dict):
        for c in self._clients.values():
            c.call_msg("register_optimizer", config=config)

    @property
    def staleness(self) -> int:
        return sum(
            msgpack.unpackb(c.call("staleness"), raw=False)["staleness"]
            for c in self._clients.values()
        )

    def ready_for_serving(self) -> bool:
        """True iff every worker replica (and through them, every PS)
        is serving."""
        try:
            return all(
                msgpack.unpackb(c.call("ready"), raw=False)["ready"]
                for c in self._clients.values()
            )
        except Exception:
            return False

    def wait_for_serving(self, timeout: float = 120.0):
        """Block until the service tier recovers (reference
        forward.rs:708-715): poll readiness with backoff."""
        import time

        deadline = time.monotonic() + timeout
        delay = 0.1
        while not self.ready_for_serving():
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"service tier not serving after {timeout}s")
            time.sleep(delay)
            delay = min(delay * 2, 2.0)

    def dump(self, path: str):
        from persia_tpu.pipeline import flush_backward_engines

        # quiesce in-flight async gradient updates registered on THIS
        # (trainer-side) object before the remote dump snapshots the PS
        flush_backward_engines(self)
        # first worker fans out to every PS (reference rpc.rs:118-121)
        self._clients[self.addrs[0]].call_msg("dump", path=path)

    def load(self, path: str):
        self._clients[self.addrs[0]].call_msg("load", path=path)

    # --- elastic-tier control plane --------------------------------------

    def apply_routing(self, table, ps_addrs: Optional[List[str]] = None
                      ) -> bool:
        """Broadcast a successor routing table (and, on scale-out, the
        grown PS address list) to EVERY worker replica — the reshard
        controller's cutover publish for a remote worker fleet. A
        partial broadcast raises :class:`PartialPublishError` carrying
        whether ANY replica applied: the controller's rollback
        decision hinges on that bit (rolling donors back while one
        replica already routes by the new epoch would split the
        fleet's view of slot ownership)."""
        applied = False
        failures = []
        for addr in self.addrs:
            try:
                rep = self._clients[addr].call_msg(
                    "apply_routing", table=table.to_bytes(),
                    ps_addrs=list(ps_addrs) if ps_addrs else None)
            except Exception as e:  # noqa: BLE001
                failures.append((addr, e))
                continue
            applied = applied or bool(rep.get("applied"))
        if failures:
            raise PartialPublishError(applied, failures)
        return applied

    def close_routing_window(self):
        for addr in self.addrs:
            self._clients[addr].call("close_routing_window")

    def shutdown(self):
        for c in self._clients.values():
            c.shutdown_server()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--replica-index", type=int,
                   default=int(os.environ.get("REPLICA_INDEX", 0)))
    p.add_argument("--replica-size", type=int,
                   default=int(os.environ.get("REPLICA_SIZE", 1)))
    p.add_argument("--coordinator",
                   default=knobs.get_raw("PERSIA_COORDINATOR_ADDR"))
    p.add_argument("--embedding-config", required=True,
                   help="embedding schema YAML")
    p.add_argument("--global-config", default=None)
    p.add_argument("--num-ps", type=int,
                   default=knobs.get("PERSIA_NUM_PS"))
    p.add_argument("--ps-addrs", default=None,
                   help="comma-separated fixed PS addresses (Infer mode)")
    p.add_argument("--enable-monitor", action="store_true",
                   default=knobs.get("PERSIA_ENABLE_MONITOR"),
                   help="estimate distinct ids per feature (HLL gauge)")
    from persia_tpu import obs_http

    obs_http.add_http_args(p)
    args = p.parse_args()
    from persia_tpu.tracing import set_service_name, start_deadlock_detection

    start_deadlock_detection()
    set_service_name(f"worker{args.replica_index}")

    schema = EmbeddingSchema.load(args.embedding_config)
    gc = GlobalConfig.load(args.global_config) if args.global_config else GlobalConfig()
    ps_resolver = None
    routing_fetch = None
    if args.ps_addrs:
        ps_addrs = args.ps_addrs.split(",")
    else:
        coord = CoordinatorClient(args.coordinator)
        ps_addrs = coord.wait_members(ROLE_PS, args.num_ps, timeout=120)

        def ps_resolver():
            return [PsClient(a) for a in
                    coord.wait_members(ROLE_PS, args.num_ps, timeout=120)]

        def routing_fetch():
            # pull-side routing distribution: the reshard controller
            # publishes successor tables to the coordinator KV; a
            # worker bounced with routing_stale fetches the epoch
            # itself instead of waiting for a push
            from persia_tpu.routing import fetch_from_coordinator

            return fetch_from_coordinator(coord)
    ps_clients = [PsClient(a) for a in ps_addrs]
    worker = EmbeddingWorker(
        schema, ps_clients,
        forward_buffer_size=gc.embedding_worker.forward_buffer_size,
        buffered_data_expired_sec=gc.embedding_worker.buffered_data_expired_sec,
        enable_monitor=args.enable_monitor,
        ps_resolver=ps_resolver,
        routing_fetch=routing_fetch,
    )
    service = WorkerService(
        worker, args.host, args.port,
        http_port=obs_http.port_from_args(args))
    _logger.info("embedding worker %d/%d listening on %s (%d PS, "
                 "sidecar %s)",
                 args.replica_index, args.replica_size, service.addr,
                 len(ps_clients),
                 service.http.addr if service.http else "off")
    obs_http.write_addr_file_from_args(service.http, args)
    if args.coordinator:
        # sidecar addr rides the registration (fleet-monitor discovery)
        CoordinatorClient(args.coordinator).register(
            ROLE_WORKER, args.replica_index, service.addr,
            http_addr=service.http.addr if service.http else None)
    service.server.serve_forever()


if __name__ == "__main__":
    main()
