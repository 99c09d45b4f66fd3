"""Incremental update manager: train -> serve online delta sync.

Re-design of rust/persia-incremental-update-manager/src/lib.rs:

- **Train side** (lib.rs:178-312): updated signs accumulate in a dedup
  buffer; when it exceeds ``incremental_buffer_size`` the current entry
  values are dumped as a timestamped packet directory
  ``inc_<ts>_<seq>/<replica>.inc`` (PSD1 layout) with an
  ``inc_update_done`` marker.
- **Infer side** (lib.rs:314-364): a scanner thread polls the directory,
  loads packets newer than the last applied one into the store, and
  tracks the sync delay.

The packet-discovery conventions (done-marker visibility, name-sorted
order, per-replica ``.inc`` files) live in :func:`ready_packets` /
:func:`packet_files`, shared with the serving tier's online delta
subscriber (:mod:`persia_tpu.online`) — one stream, two consumers:
the infer PS hot-loads whole rows, the serving cache upserts resident
hot rows directly.
"""

import json
import os
import threading
import time
from typing import List, Optional, Set

import numpy as np

from persia_tpu.logger import get_default_logger

_logger = get_default_logger(__name__)

DONE_MARKER = "inc_update_done"


def ready_packets(inc_dir: str, applied: Set[str]):
    """Yield ``(name, pkt_dir, marker_info)`` for every COMPLETE packet
    under ``inc_dir`` not already in ``applied``, in name order (names
    sort by dump timestamp). The one packet-discovery convention shared
    by the PS-side :class:`IncrementalUpdateLoader` and the serving-side
    delta subscriber (:mod:`persia_tpu.online`) — a packet is visible
    only once its done-marker exists and its directory has been renamed
    into place: a dumper SIGKILLed mid-packet leaves a ``.tmp``
    directory behind, possibly with an empty marker, which is never
    listed."""
    if not os.path.isdir(inc_dir):
        return
    for name in sorted(os.listdir(inc_dir)):
        pkt_dir = os.path.join(inc_dir, name)
        marker = os.path.join(pkt_dir, DONE_MARKER)
        if (name in applied or not name.startswith("inc_")
                or name.endswith(".tmp") or not os.path.exists(marker)):
            continue
        with open(marker) as f:
            info = json.load(f)
        yield name, pkt_dir, info


def packet_files(pkt_dir: str):
    """The ``(source_replica, path)`` pairs of one packet's ``.inc``
    files, in replica order. The file stem IS the dumping replica's
    index (the packet-name ``_r<replica>`` suffix repeats it) — the
    routing-aware consumers key ownership filtering on it."""
    out = []
    for fn in sorted(os.listdir(pkt_dir)):
        if not fn.endswith(".inc"):
            continue
        try:
            replica = int(fn[:-len(".inc")])
        except ValueError:
            continue
        out.append((replica, os.path.join(pkt_dir, fn)))
    return out


class IncrementalUpdateDumper:
    """Train-side: attach to a holder; call ``commit(signs)`` after every
    gradient update."""

    def __init__(self, holder, inc_dir: str, buffer_size: int = 1_000_000,
                 replica_index: int = 0):
        self.holder = holder
        self.inc_dir = inc_dir
        self.buffer_size = buffer_size
        self.replica_index = replica_index
        self._buffer: Set[int] = set()
        self._lock = threading.Lock()
        # Packets replay in name (= seq) order, and a packet holds the
        # rows as they read when it is DUMPED. So the seq is handed out
        # inside the dump turn: of two handlers flushing at once, the
        # later-named packet is the one whose rows were read later, and
        # a restore never ends on an older row. Guards ``_seq``.
        self._dump_turn = threading.Lock()
        self._seq = 0
        os.makedirs(inc_dir, exist_ok=True)

    def commit(self, signs: np.ndarray):
        self._buffer_and_maybe_dump(signs, force=False)

    def flush(self):
        self._buffer_and_maybe_dump((), force=True)

    def _buffer_and_maybe_dump(self, signs, force: bool):
        with self._lock:
            self._buffer.update(int(s) for s in signs)
            if not self._buffer or not (
                    force or len(self._buffer) >= self.buffer_size):
                return
            flush, self._buffer = self._buffer, set()
        with self._dump_turn:
            self._seq += 1
            self._dump_packet(flush, self._seq)

    def _dump_packet(self, signs: Set[int], seq: int):
        import struct

        from persia_tpu.ps.optim import RowPrecision
        from persia_tpu.ps.store import _DTYPE_CODES, DUMP_MAGIC

        # packets honor the holder's storage policy: a half-precision
        # holder ships v2 records (fp16/bf16 emb bytes + f32 state) —
        # half the train->serve sync bytes; the loader's version-agnostic
        # reader widens on apply. fp32 holders keep the v1 layout.
        row_dtype = getattr(self.holder, "row_dtype", "fp32")
        rp = RowPrecision(row_dtype)
        version = 1 if rp.is_fp32 else 2

        # the replica index is part of the packet NAME, not just the
        # file inside: all replicas share one inc_dir (global config),
        # and two replicas flushing in the same second used to collide
        # on the same packet directory (rename onto a non-empty dir ->
        # the update RPC that triggered the flush failed). A restarted
        # replica restarts seq at 1, so the pid suffix keeps a fresh
        # incarnation from colliding with its predecessor's packets.
        # ``seq`` is allocated inside the dump turn:
        # concurrent update handlers (dispatch pool, shard-parallel)
        # both flushing used to race the unguarded `self._seq += 1`
        # here and could mint the SAME packet name within one second of
        # one pid — the within-replica twin of the cross-replica
        # collision above, surfaced by persialint's lock pass.
        name = (f"inc_{time.strftime('%Y%m%d%H%M%S')}_{seq:06d}"
                f"_r{self.replica_index}_p{os.getpid()}")
        pkt_dir = os.path.join(self.inc_dir, name)
        tmp_dir = pkt_dir + ".tmp"
        os.makedirs(tmp_dir, exist_ok=True)
        path = os.path.join(tmp_dir, f"{self.replica_index}.inc")
        records = []
        count = 0
        for sign in signs:
            entry = self.holder.get_entry(sign)
            if entry is None:
                continue
            dim, vec = entry
            vec = np.ascontiguousarray(vec, np.float32)
            if version == 1:
                records.append(struct.pack("<QII", sign, dim, len(vec)))
                records.append(vec.tobytes())
            else:
                records.append(struct.pack(
                    "<QIBI", sign, dim, _DTYPE_CODES[rp.name],
                    len(vec) - dim))
                records.append(rp.pack(vec, dim).tobytes())
            count += 1
        with open(path, "wb") as f:
            f.write(DUMP_MAGIC)
            f.write(struct.pack("<IQ", version, count))
            for r in records:
                f.write(r)
        with open(os.path.join(tmp_dir, DONE_MARKER), "w") as f:
            json.dump({"count": count, "time": time.time()}, f)
        os.rename(tmp_dir, pkt_dir)
        _logger.info("incremental packet %s: %d entries", name, count)


class IncrementalUpdateLoader:
    """Infer-side: scan ``inc_dir`` and hot-load new packets.

    ``replica_index`` restricts the load to that replica's ``.inc``
    files — the crash-recovery boot replay uses this so a restored PS
    shard reconstructs exactly ITS rows (all replicas share one
    inc_dir); the default (None) keeps the infer-side behavior of
    loading every replica's entries.

    ``routing`` (a :class:`~persia_tpu.routing.RoutingTable`) replaces
    the filename filter with OWNERSHIP filtering: every replica's
    packets are read, and only entries the table routes to
    ``replica_index`` apply. This is the correct replay across a
    shard-count change — a replica recovering after a 2→3 reshard must
    reconstruct the rows it owns NOW, which live scattered across the
    old fleet's packet files, and must never apply rows it no longer
    owns (they would shadow the live owner's state at the next
    checkpoint merge)."""

    def __init__(self, holder, inc_dir: str, scan_interval_sec: float = 10.0,
                 replica_index: Optional[int] = None, routing=None):
        self.holder = holder
        self.inc_dir = inc_dir
        self.scan_interval_sec = scan_interval_sec
        self.replica_index = replica_index
        self.routing = routing
        if routing is not None and replica_index is None:
            raise ValueError(
                "routing-filtered replay needs the replica_index the "
                "table should route to")
        self._applied: Set[str] = set()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.last_delay_sec: float = 0.0
        self.packets_applied: int = 0
        # Serving-freshness observables: last_delay_sec existed but was
        # never exported — it now rides the registry as a gauge, and the
        # per-packet sign-to-servable age (apply time minus the packet's
        # dump timestamp) lands in an age-shaped histogram, so "how
        # stale is serving" is a distribution, not one scan-time point.
        from persia_tpu.metrics import (AGE_BUCKETS, COUNT_BUCKETS,
                                        default_registry)

        reg = default_registry()
        self._g_delay = reg.gauge(
            "inc_update_last_delay_sec",
            help_text="age of the newest applied incremental packet at "
                      "its apply time (train->serve sync delay)")
        # The STALL signal: last_delay_sec freezes at its last healthy
        # value when packets stop arriving (it is only written on
        # apply), so detecting a dead sync loop needs a clock that
        # keeps running — seconds since the last apply (or since this
        # loader armed, so a dumper dead from boot also trips it),
        # refreshed on EVERY scan whether or not anything applied.
        self._t_last_apply = time.monotonic()
        self._g_since_apply = reg.gauge(
            "inc_update_sec_since_last_apply",
            help_text="seconds since this loader last applied a packet "
                      "(or since it started) — keeps rising while the "
                      "train->serve sync loop is stalled")
        self._h_freshness = reg.histogram(
            "inc_update_freshness_lag_sec",
            help_text="per-packet sign-to-servable age: packet dump "
                      "timestamp to its apply completing",
            buckets=AGE_BUCKETS)
        self._h_entries = reg.histogram(
            "inc_update_packet_entries",
            help_text="entries loaded per applied incremental packet",
            buckets=COUNT_BUCKETS)
        self._c_packets = reg.counter(
            "inc_update_packets_applied_total",
            help_text="incremental packets applied by this loader")
        self._c_entries = reg.counter(
            "inc_update_entries_applied_total",
            help_text="entries hot-loaded from incremental packets")

    def scan_once(self) -> int:
        """Apply any unapplied complete packets; returns entries loaded."""
        from persia_tpu.checkpoint import iter_psd_entries

        loaded = 0
        for name, pkt_dir, info in ready_packets(self.inc_dir,
                                                 self._applied):
            pkt_loaded = 0
            for src, path in packet_files(pkt_dir):
                if (self.routing is None and self.replica_index is not None
                        and src != self.replica_index):
                    continue
                if self.routing is not None:
                    # ownership replay: read EVERY replica's file,
                    # batch the entries, and keep only the rows the
                    # NEW table routes here — the filename filter
                    # encodes the old fleet's shard count and is
                    # wrong the moment it changes
                    batch = list(iter_psd_entries(path))
                    if not batch:
                        continue
                    owners = self.routing.replica_of(np.array(
                        [b[0] for b in batch], dtype=np.uint64))
                    for (sign, dim, vec), owner in zip(batch, owners):
                        if int(owner) != self.replica_index:
                            continue
                        self.holder.set_entry(sign, dim, vec)
                        pkt_loaded += 1
                    continue
                for sign, dim, vec in iter_psd_entries(path):
                    self.holder.set_entry(sign, dim, vec)
                    pkt_loaded += 1
            loaded += pkt_loaded
            self._applied.add(name)
            # freshness lag measured when the packet's rows are
            # SERVABLE (apply done), against its dump timestamp —
            # the per-packet distribution; last_delay_sec stays the
            # scan-time scalar callers already read
            self.last_delay_sec = max(0.0, time.time() - info["time"])
            self.packets_applied += 1
            self._h_freshness.observe(self.last_delay_sec)
            self._h_entries.observe(pkt_loaded)
            self._c_packets.inc()
            self._c_entries.inc(pkt_loaded)
            self._g_delay.set(self.last_delay_sec)
            self._t_last_apply = time.monotonic()
        self._g_since_apply.set(self.sec_since_last_apply)
        return loaded

    @property
    def sec_since_last_apply(self) -> float:
        return max(0.0, time.monotonic() - self._t_last_apply)

    def start(self):
        def run():
            while not self._stop.wait(self.scan_interval_sec):
                try:
                    self.scan_once()
                except Exception as e:  # keep scanning on bad packets
                    _logger.error("incremental scan failed: %s", e)

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="inc-update-scanner")
        self._thread.start()

    def stop(self):
        self._stop.set()
