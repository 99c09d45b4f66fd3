"""Autopilot decision-engine tests over injected time: policy
hysteresis and hold semantics, cooldown/rate-limit gating (armed
identically in recommend and enforce mode), the action journal's
record format + crash-safe on-disk reload, deferred outcome
verification (a scale-IN watches the HIGH-load rule — load staying low
is the point), and the recommend-mode wire-neutrality pin against a
live in-process PS."""

import json
import os

import pytest

from persia_tpu.autopilot import (ActionJournal, Autopilot,
                                  PsScalePolicy, RebalancePolicy,
                                  VariantShedPolicy, default_policies)
from persia_tpu.fleet import FleetHistory
from persia_tpu.slos import SloEngine


class SpyRecorder:
    def __init__(self):
        self.captures = []

    def capture(self, service, reason, extra=None):
        self.captures.append((service, reason, extra))


class FakeMonitor:
    """A real SLO engine + real history ring fed by hand with explicit
    timestamps — the pilot only ever reads these, so nothing else of
    the fleet plane is needed."""

    def __init__(self):
        self.engine = SloEngine()
        self.history = FleetHistory()
        self.recorder = None
        self.plan = None

    def feed(self, service, rows_rate, t):
        samples = [("ps_lookup_row_rate", {}, float(rows_rate))]
        self.engine.ingest(service, samples, t=t)
        self.history.record(service, samples, t=t)

    def hotness_plan(self, num_replicas, num_slots=None,
                     current_table=None):
        if self.plan is None:
            raise RuntimeError("no hotness telemetry")
        return dict(self.plan)


class FakeOperator:
    def __init__(self, replicas=2):
        self._replicas = {"job": replicas}
        self.calls = []

    def ps_replicas(self, job):
        return self._replicas[job]

    def scale_ps(self, job, replicas):
        self.calls.append(("scale_ps", job, replicas))
        self._replicas[job] = replicas
        return {"job": job, "to": replicas, "status": "done"}

    def rebalance_ps(self, job):
        self.calls.append(("rebalance_ps", job))
        return {"job": job, "phase": "rebalance", "status": "done"}

    def variant_op(self, job, op, payload):
        self.calls.append(("variant_op", job, op, dict(payload)))
        return {"job": job, "op": op, "status": "done"}


def _mk_scale_pilot(mode="enforce", journal_dir=None, cooldown=0.0,
                    per_hour=100, replicas=2, verify_sec=30.0):
    mon, op = FakeMonitor(), FakeOperator(replicas=replicas)
    policy = PsScalePolicy("job", scale_out_at=100.0,
                           scale_in_below=20.0, window_sec=10.0,
                           min_replicas=2, max_replicas=4,
                           verify_sec=verify_sec)
    pilot = Autopilot(mon, op, "job", policies=[policy], mode=mode,
                      journal_dir=journal_dir, cooldown_sec=cooldown,
                      max_actions_per_hour=per_hour)
    return mon, op, policy, pilot


def _feed_window(mon, per_service, t0, t1, step=2.0):
    t = t0
    while t <= t1:
        for svc, v in per_service.items():
            mon.feed(svc, v, t)
        t += step


def _tick(pilot, mon, now):
    return pilot.tick(now, mon.engine.evaluate(now))


def test_scale_policy_hysteresis_band():
    mon, op, _policy, pilot = _mk_scale_pilot()
    # sustained high: both replicas hold 80 rows/s across the whole
    # window -> fleet sum of window-minima 160 > 100
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 0.0, 10.0)
    decisions = _tick(pilot, mon, 10.0)
    assert [d["kind"] for d in decisions] == ["scale_out"]
    assert op.calls == [("scale_ps", "job", 3)]
    assert op.ps_replicas("job") == 3

    # mid-band (sum 60: between 20 and 100) holds the size
    _feed_window(mon, {"ps0": 30.0, "ps1": 30.0}, 12.0, 24.0)
    assert _tick(pilot, mon, 24.0) == []
    assert op.ps_replicas("job") == 3

    # sustained low (sum of window-maxima 10 < 20) -> scale back in
    _feed_window(mon, {"ps0": 5.0, "ps1": 5.0}, 26.0, 38.0)
    decisions = _tick(pilot, mon, 38.0)
    assert [d["kind"] for d in decisions] == ["scale_in"]
    assert op.ps_replicas("job") == 2

    # at the floor, sustained low proposes nothing
    _feed_window(mon, {"ps0": 5.0, "ps1": 5.0}, 40.0, 52.0)
    assert _tick(pilot, mon, 52.0) == []


def test_one_spike_is_not_sustained():
    mon, op, _policy, pilot = _mk_scale_pilot()
    # one scrape spikes far over the threshold; the rest of the
    # window sits below it — sustained() (window min) must hold fire
    _feed_window(mon, {"ps0": 40.0, "ps1": 40.0}, 0.0, 4.0)
    mon.feed("ps0", 5000.0, 6.0)
    mon.feed("ps1", 5000.0, 6.0)
    _feed_window(mon, {"ps0": 40.0, "ps1": 40.0}, 8.0, 10.0)
    assert _tick(pilot, mon, 10.0) == []
    assert op.calls == []


def test_journal_format_evidence_and_disk_reload(tmp_path):
    jdir = str(tmp_path / "journal")
    mon, op, _policy, pilot = _mk_scale_pilot(journal_dir=jdir)
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 0.0, 10.0)
    assert len(_tick(pilot, mon, 10.0)) == 1

    recs = ActionJournal(jdir).records()
    assert [r["kind"] for r in recs] == ["decision", "executed"]
    dec, exe = recs
    # the decision nests its payload: the record's own "kind" is the
    # record type, the ACTION kind lives inside
    assert dec["decision"]["kind"] == "scale_out"
    assert dec["decision"]["action"] == {"job": "job", "replicas": 3}
    ev = dec["decision"]["evidence"]
    assert ev["firing_rules"] and ev["history"]
    assert all(a["rule"] == "autopilot_ps_scale_load_high"
               for a in ev["firing_rules"])
    assert all(e["metric"] == "ps_lookup_row_rate" and e["points"]
               for e in ev["history"])
    assert exe["action_kind"] == "scale_out"
    assert exe["decision_seq"] == dec["decision"]["decision_seq"]
    assert exe["operator_event"]["status"] == "done"
    # every record is its own atomic file, readable in isolation
    names = sorted(os.listdir(jdir))
    assert len(names) == 2 and all(n.startswith("rec_") for n in names)
    for n in names:
        json.loads(open(os.path.join(jdir, n)).read())
    # record keys are reserved — a field cannot shadow them
    j = ActionJournal(jdir)
    with pytest.raises(ValueError):
        j.append("decision", kind="scale_out")
    with pytest.raises(ValueError):
        j.append("decision", seq=1, ts=0.0)


def test_cooldown_defers_with_reason():
    mon, op, _policy, pilot = _mk_scale_pilot(cooldown=100.0)
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 0.0, 10.0)
    assert len(_tick(pilot, mon, 10.0)) == 1
    # load still high at 3 replicas (max 4): proposal repeats but the
    # per-(policy, kind) cooldown blocks it -> deferred, no operator
    # call
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 12.0, 22.0)
    assert _tick(pilot, mon, 22.0) == []
    assert op.calls == [("scale_ps", "job", 3)]
    deferred = [r for r in pilot.journal.tail()
                if r["kind"] == "deferred"]
    assert deferred and "cooldown" in deferred[-1]["blocked_by"]
    assert deferred[-1]["action_kind"] == "scale_out"


def test_global_rate_limit():
    mon, op, _policy, pilot = _mk_scale_pilot(per_hour=1)
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 0.0, 10.0)
    assert len(_tick(pilot, mon, 10.0)) == 1
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 12.0, 22.0)
    assert _tick(pilot, mon, 22.0) == []
    deferred = [r for r in pilot.journal.tail()
                if r["kind"] == "deferred"]
    assert deferred and "rate limit" in deferred[-1]["blocked_by"]
    # the trailing-hour window forgets: an hour later the same
    # proposal clears
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 3700.0, 3710.0)
    assert len(_tick(pilot, mon, 3710.0)) == 1


def test_recommend_mode_never_touches_the_operator():
    mon, op, _policy, pilot = _mk_scale_pilot(mode="recommend")
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 0.0, 10.0)
    decisions = _tick(pilot, mon, 10.0)
    assert [d["kind"] for d in decisions] == ["scale_out"]
    assert decisions[0]["mode"] == "recommend"
    assert op.calls == []
    assert op.ps_replicas("job") == 2
    # journaled all the same — the recommend soak IS the audit trail
    kinds = [r["kind"] for r in pilot.journal.tail()]
    assert kinds == ["decision"]


def test_recommend_matches_enforce_decision_for_decision():
    mon = FakeMonitor()
    op = FakeOperator(replicas=2)

    def mk(mode):
        return Autopilot(
            mon, op, "job",
            policies=[PsScalePolicy("job", scale_out_at=100.0,
                                    scale_in_below=20.0,
                                    window_sec=10.0, min_replicas=2,
                                    max_replicas=4, verify_sec=5.0)],
            mode=mode, cooldown_sec=0.0, max_actions_per_hour=100)

    # shadow shares the operator (reads the same observed replica
    # counts) and ticks FIRST, before enforcement mutates the world
    shadow, enforce = mk("recommend"), mk("enforce")
    rec, enf = [], []
    script = [({"ps0": 80.0, "ps1": 80.0}, 10.0),   # -> scale_out
              ({"ps0": 30.0, "ps1": 30.0}, 24.0),   # hold
              ({"ps0": 5.0, "ps1": 5.0}, 38.0)]     # -> scale_in
    t_prev = 0.0
    for load, t_end in script:
        _feed_window(mon, load, t_prev + 2.0, t_end)
        alerts = mon.engine.evaluate(t_end)
        rec.extend(shadow.tick(t_end, alerts))
        enf.extend(enforce.tick(t_end, alerts))
        t_prev = t_end

    key = [(d["policy"], d["kind"], d["action"]) for d in rec]
    assert key == [(d["policy"], d["kind"], d["action"]) for d in enf]
    assert [k[1] for k in key] == ["scale_out", "scale_in"]
    # only the enforce pilot acted
    assert op.calls == [("scale_ps", "job", 3), ("scale_ps", "job", 2)]


def test_rebalance_hold_min_gain_and_hysteresis():
    mon = FakeMonitor()
    op = FakeOperator(replicas=2)
    policy = RebalancePolicy("job", share_threshold=0.6, hold_sec=5.0,
                             min_gain=0.05, window_sec=10.0,
                             verify_sec=30.0)
    pilot = Autopilot(mon, op, "job", policies=[policy],
                      mode="enforce", cooldown_sec=0.0,
                      max_actions_per_hour=100)
    # ps0 carries 90% — breach, but it must HOLD for hold_sec first
    _feed_window(mon, {"ps0": 90.0, "ps1": 10.0}, 0.0, 10.0)
    mon.plan = {"assignment": [0, 1], "max_replica_share": 0.5,
                "hash_even_max_share": 0.9, "moved_slots": 1,
                "slot_weights": [90.0, 10.0]}
    assert _tick(pilot, mon, 10.0) == []       # pending starts
    assert _tick(pilot, mon, 13.0) == []       # 3s held < 5s
    # held long enough, but a plan that cannot help blocks the move
    mon.plan["max_replica_share"] = 0.88       # 0.9 - 0.05 < 0.88
    assert _tick(pilot, mon, 16.0) == []
    mon.plan["max_replica_share"] = 0.5
    decisions = _tick(pilot, mon, 17.0)
    assert [d["kind"] for d in decisions] == ["rebalance"]
    assert decisions[0]["plan"]["max_replica_share"] == 0.5
    assert decisions[0]["plan"]["measured_shares"]["ps0"] > 0.8
    assert op.calls == [("rebalance_ps", "job")]
    # hysteresis: once the share clears the band, a NEW breach starts
    # a fresh hold — no instant re-fire off stale pending state
    _feed_window(mon, {"ps0": 50.0, "ps1": 50.0}, 19.0, 29.0)
    assert _tick(pilot, mon, 29.0) == []
    _feed_window(mon, {"ps0": 90.0, "ps1": 10.0}, 31.0, 41.0)
    assert _tick(pilot, mon, 41.0) == []       # held 0s: pending only
    assert _tick(pilot, mon, 47.0) != []       # held >5s: fires again


def test_scale_in_watches_the_high_rule_not_the_low_one():
    mon, op, _policy, pilot = _mk_scale_pilot(replicas=3,
                                              verify_sec=5.0)
    # sustained low at 3 replicas -> scale_in executes
    _feed_window(mon, {"ps0": 5.0, "ps1": 5.0}, 0.0, 10.0)
    assert [d["kind"] for d in _tick(pilot, mon, 10.0)] == ["scale_in"]
    # load STAYS low through the verify window — the low rule still
    # fires, and that is exactly what a correct shrink looks like:
    # the verdict must be improved, not regressed
    _feed_window(mon, {"ps0": 5.0, "ps1": 5.0}, 12.0, 16.0)
    _tick(pilot, mon, 16.0)
    kinds = [r["kind"] for r in pilot.journal.tail()]
    assert "outcome" in kinds and "regressed" not in kinds
    outcome = [r for r in pilot.journal.tail()
               if r["kind"] == "outcome"][-1]
    assert outcome["action_kind"] == "scale_in" and outcome["improved"]


def test_scale_out_regression_captures_postmortem():
    mon, op, _policy, pilot = _mk_scale_pilot(verify_sec=5.0)
    spy = SpyRecorder()
    mon.recorder = spy
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 0.0, 10.0)
    assert [d["kind"] for d in _tick(pilot, mon, 10.0)] == ["scale_out"]
    # the high rule is STILL firing after the verify window: the
    # scale-out did not move its target signal
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 12.0, 16.0)
    _tick(pilot, mon, 16.0)
    regressed = [r for r in pilot.journal.tail()
                 if r["kind"] == "regressed"]
    assert len(regressed) == 1
    assert regressed[0]["action_kind"] == "scale_out"
    assert regressed[0]["watch_rule"] == "autopilot_ps_scale_load_high"
    assert len(spy.captures) == 1
    service, reason, _extra = spy.captures[0]
    assert service in ("ps0", "ps1")
    assert reason == "autopilot_regressed:scale_out"


def test_variant_shed_from_by_label_alert():
    mon = FakeMonitor()
    op = FakeOperator()
    pilot = Autopilot(mon, op, "job",
                      policies=[VariantShedPolicy("job", shed_to=0.1)],
                      mode="enforce", cooldown_sec=0.0,
                      max_actions_per_hour=100)
    alerts = [{"rule": "variant_degraded", "firing": True,
               "service": "serving0[variant=canary]", "value": 0.4,
               "expr": "ratio(bad, all)", "op": ">", "threshold": 0.25,
               "firing_since": 1.0}]
    decisions = pilot.tick(10.0, alerts)
    assert [d["kind"] for d in decisions] == ["variant_shed"]
    assert decisions[0]["action"] == {"job": "job", "name": "canary",
                                      "weight": 0.1}
    assert op.calls == [("variant_op", "job", "weight",
                         {"name": "canary", "weight": 0.1})]
    # evidence carries the triggering by_label alert itself
    ev = decisions[0]["evidence"]
    assert ev["firing_rules"][0]["service"] == \
        "serving0[variant=canary]"


def test_failed_action_is_journaled_not_raised():
    mon, op, _policy, pilot = _mk_scale_pilot()

    def boom(job, replicas):
        raise RuntimeError("kube apiserver down")

    op.scale_ps = boom
    _feed_window(mon, {"ps0": 80.0, "ps1": 80.0}, 0.0, 10.0)
    decisions = _tick(pilot, mon, 10.0)   # must not raise
    assert len(decisions) == 1
    recs = pilot.journal.tail()
    failed = [r for r in recs if r["kind"] == "action_failed"]
    assert len(failed) == 1
    assert failed[0]["action_kind"] == "scale_out"
    assert "kube apiserver down" in failed[0]["error"]
    assert not [r for r in recs if r["kind"] == "executed"]


def test_default_policies_shape_and_describe():
    policies = default_policies("job")
    assert [p.name for p in policies] == ["ps_scale", "ps_rebalance",
                                         "variant_shed"]
    mon, op = FakeMonitor(), FakeOperator()
    pilot = Autopilot(mon, op, "job", mode="recommend")
    doc = pilot.describe()
    assert doc["mode"] == "recommend"
    assert doc["policies"] == ["ps_scale", "ps_rebalance",
                               "variant_shed"]
    assert doc["actions_trailing_hour"] == 0
    # the policies' rules joined the monitor's live alert surface
    names = {r.name for r in mon.engine.rules}
    assert {"autopilot_ps_scale_load_high",
            "autopilot_ps_scale_load_low"} <= names


def test_recommend_pilot_is_wire_neutral_against_live_ps():
    """The pull-only pin: a recommend-mode pilot driving scrapes and
    ticks over a LIVE PS adds zero requests on the RPC plane."""
    from persia_tpu.fleet import FleetMonitor
    from persia_tpu.metrics import default_registry
    from persia_tpu.obs_http import ObservabilityServer
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service.ps_service import PsService

    svc = PsService(EmbeddingHolder(capacity=10_000, hotness=True),
                    port=0)
    svc.server.serve_background()
    side = ObservabilityServer(
        registry=default_registry(), health_fn=svc._health,
        service="ps0", refresh_fn=svc._refresh_mem_gauges,
        hotness_fn=svc._hotness_snapshot).start()
    mon = FleetMonitor(
        targets=[{"service": "ps0", "http_addr": side.addr,
                  "role": "ps"}])
    pilot = Autopilot(mon, FakeOperator(), "job", mode="recommend",
                      cooldown_sec=0.0, max_actions_per_hour=100)
    try:
        before = svc.server.health()["served_rpcs"]
        for _ in range(3):
            mon.scrape_once()
            pilot.tick()
        assert svc.server.health()["served_rpcs"] == before == 0
    finally:
        mon.stop()
        side.stop()
        svc.stop()


# --- the closed loop against a live fleet ----------------------------------


class _OneServerRegistry:
    """Render view of the process registry restricted to one PS
    server's labeled series. The replicas below run in-process and share
    the process-wide registry; each sidecar must expose only ITS
    replica's series (what separate processes would serve) or the fleet
    sum and the per-replica share breakdown would count every replica
    four times."""

    def __init__(self, base, server_label):
        self._base = base
        self._needle = f'server="{server_label}"'

    def histogram(self, *a, **kw):
        return self._base.histogram(*a, **kw)

    def render(self):
        keep = [line for line in self._base.render().splitlines()
                if line.startswith("#") or self._needle in line]
        return "\n".join(keep) + "\n"


def test_scripted_ramp_drives_live_fleet_through_three_actions(tmp_path):
    """Telemetry → planner → operator, unattended: a scripted load/skew
    ramp drives a live counting-optimizer PS fleet (4 in-process
    replicas, each behind its own sidecar) while an ENFORCE pilot and a
    shadow RECOMMEND pilot tick over the same fleet monitor. The pilot
    acts through the k8s operator's drivers with a live
    ReshardController doing the slot migration. Held to:

    - exactly scale_out (2→3) → rebalance → scale_in (3→2), every
      action's deferred verification ``improved``, none regressed or
      failed, the fleet back at 2 replicas;
    - zero lost updates across all three (the counting identity);
    - the shadow pilot, stepped at the same (now, alerts) instants,
      decides (policy, kind, action) exactly as enforce did;
    - the on-disk journal re-reads one decision per action with the
      history excerpt (and, for scale actions, the firing rules).

    Thresholds are fractions of this machine's own measured unpaced row
    rate, so the ramp crosses the same hysteresis bands on a loaded
    runner as on a fast workstation."""
    import threading
    import time

    import numpy as np

    from persia_tpu.config import EmbeddingSchema, uniform_slots
    from persia_tpu.data.batch import IDTypeFeature
    from persia_tpu.fleet import FleetMonitor
    from persia_tpu.k8s_operator import FakeKubeApi, Operator
    from persia_tpu.metrics import default_registry
    from persia_tpu.obs_http import ObservabilityServer
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.reshard import ReshardController
    from persia_tpu.routing import RoutingTable
    from persia_tpu.service.ps_service import PsClient, PsService
    from persia_tpu.slos import default_rules
    from persia_tpu.worker.worker import EmbeddingWorker
    from tests.fleet_support import (
        arm_counting,
        owner_filtered_applied,
        time_limit,
        unit_update,
    )

    SCRAPE, WINDOW = 0.2, 1.6
    dim, n_feats, n_threads, bs = 8, 2, 2, 256
    job, sign_space = "bench", 1 << 20
    schema = EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{i}" for i in range(n_feats)], dim=dim))

    holders, services, clients, sidecars = [], [], [], []
    for i in range(4):
        h = EmbeddingHolder(capacity=2_000_000, hotness=True)
        svc = PsService(h, port=0)
        svc.server.serve_background()
        c = PsClient(svc.addr, circuit_breaker=False)
        arm_counting(c)
        sidecars.append(ObservabilityServer(
            port=0,
            registry=_OneServerRegistry(default_registry(),
                                        svc.addr.rsplit(":", 1)[1]),
            health_fn=svc._health, service=f"ps{i}",
            refresh_fn=svc._refresh_mem_gauges,
            hotness_fn=svc._hotness_snapshot).start())
        holders.append(h)
        services.append(svc)
        clients.append(c)

    table = RoutingTable.uniform(2)
    worker = EmbeddingWorker(schema, clients[:2], routing=table)
    controller = ReshardController(clients[:2], table, workers=[worker],
                                   replay_settle_rows=64, drain_sec=0.25)
    last_table = [table]
    jdir = str(tmp_path / "journal")
    monitor = FleetMonitor(
        targets=[{"service": f"ps{i}", "http_addr": s.addr,
                  "role": "ps", "replica": i}
                 for i, s in enumerate(sidecars)],
        scrape_interval=SCRAPE, scrape_timeout=1.0, flight_interval=4.0,
        slo_engine=SloEngine(default_rules()),
        postmortem_dir=str(tmp_path / "postmortems"))

    def reshard_driver(job_name, old, new, phase, spec):
        if phase == "resume":
            return
        if phase == "rebalance":
            plan = monitor.hotness_plan(old, current_table=last_table[0])
            last_table[0] = controller.reshard_to(
                old, slot_weights=np.asarray(plan["slot_weights"],
                                             np.float64))
        elif phase == "scale_out":
            last_table[0] = controller.reshard_to(
                new, new_ps_clients=clients[:new])
        else:  # scale_in
            last_table[0] = controller.reshard_to(new)

    operator = Operator(FakeKubeApi(), [{
        "jobName": job,
        "image": "persia-tpu-runtime:bench",
        "embeddingConfigPath": "/config/embedding_config.yml",
        "roles": {
            "embeddingParameterServer": {"replicas": 2},
            "embeddingWorker": {"replicas": 1},
            "nnWorker": {"replicas": 1, "entry": "train.py"},
        },
    }], interval=60.0, reshard_driver=reshard_driver)

    # paced trainer threads: the offered load the script ramps
    ships = [0]
    s_lock = threading.Lock()
    stop = threading.Event()
    errors = []
    period_box = [0.0]  # per-thread seconds/cycle; 0 = unpaced
    hot_box = [np.zeros(0, dtype=np.uint64)]

    def draws(rng):
        if len(hot_box[0]):
            n_hot = int(bs * 0.75)
            return [np.concatenate([
                rng.choice(hot_box[0], size=n_hot),
                rng.integers(0, sign_space, bs - n_hot, dtype=np.uint64)])
                for _ in range(n_feats)]
        return [rng.integers(0, sign_space, bs, dtype=np.uint64)
                for _ in range(n_feats)]

    def train(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            feats = [IDTypeFeature(f"slot_{i}", [r])
                     for i, r in enumerate(draws(rng))]
            t0 = time.perf_counter()
            try:
                unit_update(worker, feats)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return
            with s_lock:
                ships[0] += n_feats * bs
            spare = period_box[0] - (time.perf_counter() - t0)
            if spare > 0:
                time.sleep(spare)

    threads = [threading.Thread(target=train, args=(s,))
               for s in range(n_threads)]
    for t in threads:
        t.start()
    enf_decisions, rec_decisions = [], []
    try:
        with time_limit(120, "autopilot scripted ramp"):
            # calibration: this machine's unpaced row rate
            t_cal0, ships0 = time.monotonic(), ships[0]
            while time.monotonic() - t_cal0 < 1.0:
                time.sleep(SCRAPE)
                monitor.scrape_once()
            cal_sec = time.monotonic() - t_cal0
            m_cycles = max(
                (ships[0] - ships0) / (n_feats * bs) / cal_sec, 1.0)
            m_rows = monitor.history.avg_over(
                "ps_lookup_row_rate", 0.8, r"^ps", time.monotonic())
            assert m_rows and m_rows > 0, \
                "calibration saw no ps_lookup_row_rate"

            def mk_pilot(mode, journal_dir=None):
                # the cooldown holds a repeat of the SAME (policy, kind)
                # and never delays the next kind: longer than the whole
                # script, so the near-idle tail (3-5 s after the
                # rebalance) cannot draw a second rebalance from noise
                return Autopilot(
                    monitor, operator, job,
                    policies=[
                        PsScalePolicy(job, scale_out_at=0.30 * m_rows,
                                      scale_in_below=0.15 * m_rows,
                                      window_sec=WINDOW, min_replicas=2,
                                      max_replicas=3, verify_sec=1.6),
                        RebalancePolicy(job, share_threshold=0.60,
                                        hold_sec=0.8, min_gain=0.05,
                                        window_sec=1.2, verify_sec=1.6)],
                    mode=mode, journal_dir=journal_dir, cooldown_sec=30.0,
                    max_actions_per_hour=6,
                    table_fn=lambda: last_table[0])

            # shadow FIRST each tick: it must read the world as enforce
            # will the instant before enforcement mutates it
            shadow = mk_pilot("recommend")
            pilot = mk_pilot("enforce", journal_dir=jdir)

            def journaled(kind):
                return [r for r in pilot.journal.tail(256)
                        if r["kind"] == kind]

            def executed_kinds():
                return [r["action_kind"] for r in journaled("executed")]

            def drive(frac, done_fn, max_sec, label):
                """One script phase: pace the trainers at ``frac`` of
                the calibrated rate, scrape + tick both pilots every
                round, until ``done_fn`` (or, without one, for
                ``max_sec``)."""
                period_box[0] = n_threads / (frac * m_cycles)
                t_end = time.monotonic() + max_sec
                while time.monotonic() < t_end:
                    time.sleep(SCRAPE)
                    assert not errors, \
                        f"trainer thread died during {label}: {errors[0]!r}"
                    monitor.scrape_once()
                    now = time.monotonic()
                    alerts = monitor.engine.evaluate(now)
                    rec_decisions.extend(shadow.tick(now, alerts))
                    enf_decisions.extend(pilot.tick(now, alerts))
                    if done_fn is not None and done_fn():
                        return
                assert done_fn is None, (
                    f"autopilot script never reached '{label}' within "
                    f"{max_sec:.0f}s (executed so far: "
                    f"{executed_kinds()})")

            # 1. quiet warm-up fills the sustained() windows; the low
            # rule fires but 2 replicas is already the floor
            drive(0.10, None, 1.8, "warmup")
            assert not executed_kinds(), "acted during quiet warm-up"
            # 2. sustained surge -> scale_out 2→3
            drive(0.55, lambda: "scale_out" in executed_kinds(), 15.0,
                  "scale_out")
            # 3. hot-key skew on replica 0 -> rebalance at 3
            cand = np.random.default_rng(7).integers(
                0, sign_space, 8192, dtype=np.uint64)
            hot_box[0] = cand[last_table[0].replica_of(cand) == 0][:512]
            drive(0.25, lambda: "rebalance" in executed_kinds(), 18.0,
                  "rebalance")
            # 4. sustained calm -> scale_in 3→2
            hot_box[0] = np.zeros(0, dtype=np.uint64)
            drive(0.05, lambda: "scale_in" in executed_kinds(), 15.0,
                  "scale_in")
            # 5. settle until every deferred verification lands
            drive(0.05, lambda: len(journaled("outcome")) >= 3, 10.0,
                  "outcome verification")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    try:
        assert not errors, f"trainer thread died: {errors[0]!r}"
        assert not any(t.is_alive() for t in threads)
        controller.finalize(drain_sec=0.0)
        applied = owner_filtered_applied(holders, last_table[0], dim)
        assert abs(ships[0] - applied) <= 1e-3, (ships[0], applied)

        by_kind = {}
        for r in ActionJournal(jdir).records():
            by_kind.setdefault(r["kind"], []).append(r)
        assert [r["action_kind"] for r in by_kind.get("executed", [])] \
            == ["scale_out", "rebalance", "scale_in"]
        assert len([r for r in by_kind.get("outcome", [])
                    if r.get("improved")]) >= 3
        assert not by_kind.get("regressed")
        assert not by_kind.get("action_failed")
        assert operator.ps_replicas(job) == 2

        def key(ds):
            return [(d["policy"], d["kind"], d["action"]) for d in ds]

        assert key(rec_decisions) == key(enf_decisions)

        decisions = [r["decision"] for r in by_kind.get("decision", [])]
        assert len(decisions) == 3
        for d in decisions:
            ev = d.get("evidence", {})
            assert ev.get("history"), d
            if d["kind"] in ("scale_out", "scale_in"):
                assert ev.get("firing_rules"), d
    finally:
        worker.close()
        for s in services:
            s.stop()
        for side in sidecars:
            side.stop()
