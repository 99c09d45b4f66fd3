"""Online learning loop: stream trainer deltas into live serving.

PR 8 built the clocks (``inc_update_freshness_lag_sec``, the stall
SLO) and PR 1 the read-only hot-row cache, but the loop between a
trained sign update and a servable row was only closed by TTL expiry:
a row the trainer just moved stayed stale in every serving replica's
cache for up to ``cache_ttl_sec``. This module closes it directly:

- :class:`DeltaSubscriber` attaches to an ``InferenceServer``'s
  :class:`~persia_tpu.serving.HotRowCache` and scans the SAME
  incremental-update packet stream the infer-tier PS loader consumes
  (:mod:`persia_tpu.inc_update` — one wire, two subscribers), applying
  each packet's rows to RESIDENT cache entries as a **versioned
  in-place upsert**: no inserts, no evictions, no TTL dependence —
  a delta-applied row refreshes its version and TTL stamp atomically,
  so a concurrent predict either sees the whole old row or the whole
  new row, and a stale PS fetch can never resurrect the pre-delta
  value (the cache's ``put`` is version-guarded).
- A **write-rate governor** (token bucket over applied rows,
  ``PERSIA_ONLINE_APPLY_ROWS_PER_SEC``) bounds how hard a training
  burst can hammer the cache lock: a multi-million-row flush spreads
  its applies instead of convoying the predict path.
- **Routing awareness** across reshard epochs (PR 11/12): each packet
  file names its dumping PS replica; with a routing view attached, a
  row only applies when that replica OWNS the row's slot under the
  live table (or the double-read predecessor while the migration
  window is open). A donor's late packet flushed after cutover can
  therefore never shadow the new owner's fresher rows — the same
  one-owner discipline the loader's ownership replay enforces.
- The end-to-end age lands in ``serving_sign_to_servable_lag_sec``
  (packet dump timestamp -> apply completed in the serving cache) and
  the per-replica stall clock ``inc_update_sec_since_last_apply``
  (label ``consumer="serving"``), so the existing
  ``serving_freshness_stale`` SLO fires per SERVING replica, not just
  per PS.

Off is free: a server that never attaches a subscriber runs exactly
the PR-13 code — no thread, no extra RPCs, byte-identical wire
(served-request counts pinned by
``tests/test_online.py::test_serving_loop_split_oracle_freshness_and_idle_wire``).
"""

import threading
import time
from typing import Dict, Optional, Set, Tuple

import numpy as np

from persia_tpu import knobs
from persia_tpu.inc_update import packet_files, ready_packets
from persia_tpu.logger import get_default_logger

_logger = get_default_logger(__name__)

# sign-to-servable ages in seconds: the subscriber regime is sub-second
# to seconds (scan interval + governor), the TTL-only regime tens of
# seconds — both must resolve (AGE_BUCKETS starts at 0.5s, too coarse
# for the fast half of the A/B this histogram exists to judge)
LAG_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
               120.0, 300.0, 600.0)


class RateGovernor:
    """Token bucket over applied rows (1s burst). ``spend(rows)``
    blocks until the budget allows the batch and returns the seconds it
    throttled. ``rows_per_sec <= 0`` disables (never blocks). Clock and
    sleep are injectable so tests run on a fake timeline."""

    def __init__(self, rows_per_sec: float,
                 clock=time.monotonic, sleep=time.sleep):
        self.rows_per_sec = float(max(rows_per_sec, 0.0))
        self._clock = clock
        self._sleep = sleep
        self._allowance = self.rows_per_sec  # start with one full burst
        self._t_last = clock()
        self.throttled_sec = 0.0

    def spend(self, rows: int) -> float:
        if self.rows_per_sec <= 0 or rows <= 0:
            return 0.0
        now = self._clock()
        self._allowance = min(
            self.rows_per_sec,
            self._allowance + (now - self._t_last) * self.rows_per_sec)
        self._t_last = now
        if rows <= self._allowance:
            self._allowance -= rows
            return 0.0
        deficit = rows - self._allowance
        self._allowance = 0.0
        wait = deficit / self.rows_per_sec
        self._sleep(wait)
        # the slept-for tokens were consumed by this batch; advance the
        # refill origin past the sleep so they are not double-counted
        self._t_last = self._clock()
        self.throttled_sec += wait
        return wait


class DeltaSubscriber:
    """Scan the inc-update packet stream and upsert resident hot rows.

    ``routing_fn`` returns ``(table, prev)`` — the live
    :class:`~persia_tpu.routing.RoutingTable` and the double-read
    predecessor (or None) — e.g. an in-process
    ``EmbeddingWorker.routing_window``. Without it every packet's rows
    apply (the single-PS / remote-worker case).

    Single-threaded by design: one scanner thread owns ``_applied``
    and the metrics; the only shared object is the cache, whose
    versioned batch apply is the concurrency boundary with the
    predict path.
    """

    def __init__(self, cache, inc_dir: str,
                 scan_interval_sec: Optional[float] = None,
                 rows_per_sec: Optional[float] = None,
                 batch_rows: Optional[int] = None,
                 routing_fn=None,
                 consumer: str = "serving"):
        self.cache = cache
        self.inc_dir = inc_dir
        self.scan_interval_sec = float(
            scan_interval_sec if scan_interval_sec is not None
            else knobs.get("PERSIA_ONLINE_SCAN_SEC"))
        self.batch_rows = int(
            batch_rows if batch_rows is not None
            else knobs.get("PERSIA_ONLINE_APPLY_BATCH_ROWS"))
        self.governor = RateGovernor(
            rows_per_sec if rows_per_sec is not None
            else knobs.get("PERSIA_ONLINE_APPLY_ROWS_PER_SEC"))
        self.routing_fn = routing_fn
        self._applied: Set[str] = set()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.packets_applied = 0
        self.rows_applied = 0
        self.rows_skipped = 0     # not resident in the cache
        self.rows_filtered = 0    # routing says the dumper lost the row
        self.last_lag_sec = 0.0
        self.last_packet: Optional[str] = None
        self.last_packet_seq = 0
        self._t_last_apply = time.monotonic()

        from persia_tpu.metrics import default_registry

        reg = default_registry()
        labels = {"consumer": consumer}
        self._h_lag = reg.histogram(
            "serving_sign_to_servable_lag_sec", labels,
            help_text="end-to-end online-learning freshness: packet "
                      "dump timestamp to its rows being servable from "
                      "the hot-row cache (delta apply completed)",
            buckets=LAG_BUCKETS)
        self._c_packets = reg.counter(
            "serving_delta_packets_applied_total", labels,
            help_text="incremental packets the serving delta "
                      "subscriber applied into the hot-row cache")
        self._c_rows = reg.counter(
            "serving_delta_rows_applied_total", labels,
            help_text="resident hot rows upserted in place from "
                      "incremental packets")
        self._c_skipped = reg.counter(
            "serving_delta_rows_skipped_total", labels,
            help_text="packet rows ignored because the sign is not "
                      "resident in the hot-row cache (a later miss "
                      "fetches the fresh row from the PS anyway)")
        self._c_filtered = reg.counter(
            "serving_delta_rows_filtered_total", labels,
            help_text="packet rows dropped by the routing ownership "
                      "filter (the dumping replica no longer owns the "
                      "sign's slot — a stale donor packet must not "
                      "shadow the live owner)")
        self._g_throttle = reg.gauge(
            "serving_delta_throttled_sec_total", labels,
            help_text="cumulative seconds the write-rate governor "
                      "stalled delta applies to protect serving p99")
        # the per-serving-replica stall clock: SAME metric name the PS
        # loader exports, so the serving_freshness_stale SLO rule fires
        # for a serving replica whose subscriber went quiet, not just
        # for a PS whose loader did (the consumer label separates them
        # when both live in one process)
        self._g_since_apply = reg.gauge(
            "inc_update_sec_since_last_apply", labels,
            help_text="seconds since this delta subscriber last "
                      "applied a packet (or since it started) — keeps "
                      "rising while the train->serve loop is stalled")

    # --- packet application ----------------------------------------------

    def _owner_mask(self, signs: np.ndarray, src: int,
                    ) -> Optional[np.ndarray]:
        """True where the dumping replica ``src`` owns the sign under
        the live routing view (or the double-read predecessor). None =
        no routing view: apply everything."""
        if self.routing_fn is None:
            return None
        try:
            table, prev = self.routing_fn()
        except Exception:  # routing view unavailable: fail open
            return None
        if table is None:
            return None
        keep = table.replica_of(signs) == src
        if prev is not None and prev.num_slots == table.num_slots:
            keep |= prev.replica_of(signs) == src
        return keep

    def _apply_packet(self, name: str, pkt_dir: str,
                      info: Dict) -> Tuple[int, int, int]:
        from persia_tpu.checkpoint import iter_psd_entries

        applied = skipped = filtered = 0
        for src, path in packet_files(pkt_dir):
            # bucket the file's entries per dim (cache keys are
            # (dim, sign); packets interleave dims freely)
            per_dim: Dict[int, list] = {}
            for sign, dim, vec in iter_psd_entries(path):
                # packet vecs carry [emb | optimizer state]; the cache
                # stores only the embedding slice
                per_dim.setdefault(int(dim), []).append(
                    (sign, np.asarray(vec[:dim], np.float32)))
            for dim, entries in per_dim.items():
                signs = np.array([s for s, _ in entries], np.uint64)
                rows = np.stack([r for _, r in entries])
                keep = self._owner_mask(signs, src)
                if keep is not None:
                    filtered += int(len(signs) - keep.sum())
                    signs, rows = signs[keep], rows[keep]
                for at in range(0, len(signs), self.batch_rows):
                    chunk = slice(at, at + self.batch_rows)
                    self.governor.spend(len(signs[chunk]))
                    n = self.cache.apply_delta(signs[chunk], dim,
                                               rows[chunk])
                    applied += n
                    skipped += len(signs[chunk]) - n
        return applied, skipped, filtered

    def scan_once(self) -> int:
        """Apply every unapplied complete packet; returns resident rows
        upserted. Packet names are the dedup key — a packet applies
        exactly once per subscriber lifetime, whatever epochs change
        between scans."""
        total = 0
        for name, pkt_dir, info in ready_packets(self.inc_dir,
                                                 self._applied):
            applied, skipped, filtered = self._apply_packet(
                name, pkt_dir, info)
            self._applied.add(name)
            self.packets_applied += 1
            self.rows_applied += applied
            self.rows_skipped += skipped
            self.rows_filtered += filtered
            self.last_packet = name
            # inc_<ts>_<seq>_r<replica>_p<pid>
            try:
                self.last_packet_seq = int(name.split("_")[2])
            except (IndexError, ValueError):
                pass
            # sign-to-servable: the packet's rows are servable NOW
            # (apply done), against its dump timestamp
            self.last_lag_sec = max(0.0, time.time() - info["time"])
            self._h_lag.observe(self.last_lag_sec)
            self._c_packets.inc()
            self._c_rows.inc(applied)
            self._c_skipped.inc(skipped)
            self._c_filtered.inc(filtered)
            self._t_last_apply = time.monotonic()
            total += applied
        self._g_throttle.set(self.governor.throttled_sec)
        self._g_since_apply.set(self.sec_since_last_apply)
        return total

    @property
    def sec_since_last_apply(self) -> float:
        return max(0.0, time.monotonic() - self._t_last_apply)

    def health(self) -> Dict:
        """The /healthz rider: what a pager needs to judge one serving
        replica's freshness (the satellite contract — the stall clock
        and the last packet seq live HERE, per replica, not only on
        the PS loader)."""
        return {
            "sec_since_last_apply": round(self.sec_since_last_apply, 3),
            "last_lag_sec": round(self.last_lag_sec, 3),
            "last_packet": self.last_packet,
            "last_packet_seq": self.last_packet_seq,
            "packets_applied": self.packets_applied,
            "rows_applied": self.rows_applied,
            "rows_skipped": self.rows_skipped,
            "rows_filtered": self.rows_filtered,
            "throttled_sec": round(self.governor.throttled_sec, 3),
            "inc_dir": self.inc_dir,
        }

    # --- lifecycle -------------------------------------------------------

    def start(self):
        def run():
            while not self._stop.wait(self.scan_interval_sec):
                try:
                    self.scan_once()
                except Exception as e:  # keep scanning on bad packets
                    _logger.error("delta-subscriber scan failed: %s", e)

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="serving-delta-subscriber")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
