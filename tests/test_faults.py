"""Fault-tolerance layer tests: deterministic injection (faults.py),
typed RPC errors + deadline negotiation (byte-identical wire when
disabled), the per-replica circuit breaker, PS crash recovery with
checkpoint + incremental replay under the ServiceCtx supervisor,
the staleness-permit-leak regression, liveness/readiness split, and
serving's zero-vector degradation parity."""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

from persia_tpu import faults
from persia_tpu.config import EmbeddingSchema, uniform_slots
from persia_tpu.rpc import (
    CircuitBreaker,
    RpcCircuitOpen,
    RpcClient,
    RpcConnectionLost,
    RpcDeadlineExceeded,
    RpcError,
    RpcServer,
    RpcTimeout,
)

DIM = 4


@pytest.fixture(autouse=True)
def _clean_faults():
    """The injector is process-global: every test starts and ends with
    the zero-overhead disabled state (other test files assert the
    untouched wire)."""
    faults.reset_faults()
    yield
    faults.reset_faults()


# --- injection harness ----------------------------------------------------


def test_fault_rules_deterministic_counts():
    """after/times make firing exactly reproducible; seeding makes
    probabilistic rules replayable."""
    rule = faults.add("x.site", "delay", arg=0.0, after=2, times=2)
    for _ in range(6):
        faults.fire("x.site")
    assert rule.seen == 6
    assert rule.fired == 2  # skipped 2, fired 2, capped by times

    draws = []
    for _ in range(2):
        inj = faults.FaultInjector(seed=7)
        inj.add("p.site", "delay", arg=0.0, prob=0.5)
        draws.append([inj.fire("p.site") is None for _ in range(20)])
    assert draws[0] == draws[1]  # same seed, same firing pattern


def test_fault_spec_grammar_and_match_filters():
    faults.install("a.b:delay:0.01@p=0.5,after=1;ps.lookup:die:9@dim=8")
    rules = faults.default_injector().rules()
    assert rules[0] == {
        "site": "a.b", "action": "delay", "arg": 0.01, "prob": 0.5,
        "after": 1, "times": None, "match": {}, "seen": 0, "fired": 0}
    assert rules[1]["action"] == "die"
    assert rules[1]["match"] == {"dim": "8"}
    # match filter: a non-matching kwarg never fires (die would exit!)
    assert faults.fire("ps.lookup", dim=4) is None


def test_injected_connection_reset_mid_call_many():
    """An injected server-side reset mid-pipeline surfaces as the typed
    RpcConnectionLost (call_many never blind-retries — the completed
    prefix is ambiguous); after disarm the same client recovers on a
    fresh connection."""
    srv = RpcServer(concurrent_streams=4)
    srv.register("echo", lambda p: bytes(p))
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr)
        payloads = [bytes([i]) for i in range(8)]
        assert cl.call_many("echo", payloads) == payloads
        faults.add("rpc.server.recv", "reset", after=3, method="echo")
        with pytest.raises(RpcConnectionLost):
            cl.call_many("echo", payloads)
        faults.reset_faults()
        assert cl.call_many("echo", payloads) == payloads
    finally:
        srv.stop()


def test_injected_corrupt_frame_fails_request_not_connection():
    """A corrupted frame makes THAT request fail (the handler sees
    mangled bytes) while the connection — and later requests — live."""
    import msgpack

    srv = RpcServer()
    srv.register("parse", lambda p: msgpack.packb(
        msgpack.unpackb(p, raw=False)))
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr)
        good = msgpack.packb({"k": 1})
        assert cl.call("parse", good) == good
        faults.add("rpc.server.recv", "corrupt", times=1, method="parse")
        with pytest.raises(RpcError):
            cl.call("parse", good)
        assert cl.call("parse", good) == good  # same pooled connection
    finally:
        srv.stop()


def test_remote_fault_control_rpc(monkeypatch):
    """__faults__ control surface (PERSIA_FAULTS_RPC=1): a peer can arm
    and clear rules in a live server process — how a chaos test
    slows one shard of a running PS without restarting it."""
    monkeypatch.setenv("PERSIA_FAULTS_RPC", "1")
    srv = RpcServer()
    srv.register("echo", lambda p: bytes(p))
    srv.serve_background()
    try:
        faults.control(srv.addr, "rpc.server.recv:error@method=echo")
        assert faults.active()
        cl = RpcClient(srv.addr)
        with pytest.raises(RpcError, match="InjectedFault"):
            cl.call("echo", b"x")
        faults.control(srv.addr, clear=True)
        assert cl.call("echo", b"x") == b"x"
    finally:
        srv.stop()


# --- typed errors + deadlines --------------------------------------------


def test_typed_errors_subclass_legacy_exceptions():
    assert issubclass(RpcTimeout, TimeoutError)
    assert issubclass(RpcConnectionLost, ConnectionError)
    assert issubclass(RpcCircuitOpen, RpcConnectionLost)
    # dead address: the exhausted retry ladder raises the typed form
    cl = RpcClient("127.0.0.1:1", max_retries=0, retry_backoff=0.01)
    with pytest.raises(RpcConnectionLost):
        cl.call("echo", b"")


def test_deadline_sheds_expired_work_and_counts():
    srv = RpcServer(concurrent_streams=4)
    srv.register("echo", lambda p: bytes(p))
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr, deadline=30.0)
        assert cl.call("echo", b"x") == b"x"
        with pytest.raises(RpcDeadlineExceeded):
            cl.call("echo", b"x", deadline=0.0)
        # futures carry per-call deadlines through the same slot
        fut = cl.call_future("echo", b"y", deadline=0.0)
        with pytest.raises(RpcDeadlineExceeded):
            fut.result()
        assert srv.health()["shed_rpcs"] == 2
        # within-budget calls are untouched
        assert cl.call_many("echo", [b"a", b"b"], deadline=30.0) == \
            [b"a", b"b"]
    finally:
        srv.stop()


def test_deadline_negotiates_down_against_legacy_peer():
    """A deadline-armed client against a peer that refuses __deadline__
    (legacy emulation): calls run WITHOUT the slot — no shed, no error.
    Wire compatibility is what negotiate-down promises."""
    srv = RpcServer(enable_deadline=False)
    srv.register("echo", lambda p: bytes(p))
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr, deadline=0.0)  # would shed if negotiated
        assert cl.call("echo", b"x") == b"x"
        assert srv.health()["shed_rpcs"] == 0
    finally:
        srv.stop()


def test_wire_byte_identical_when_deadline_disabled():
    """Default client (no deadline): the dial sequence carries NO
    __deadline__ probe — the served-request counter sees exactly the
    application calls, same as the pre-deadline wire (the __trace__
    byte-identity discipline)."""
    srv = RpcServer()
    srv.register("echo", lambda p: bytes(p))
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr)
        assert cl.call("echo", b"x") == b"x"
        health = srv.health()
        assert health["served_rpcs"] == 1  # no probe traffic at dial
        assert health["shed_rpcs"] == 0
    finally:
        srv.stop()


# --- circuit breaker ------------------------------------------------------


def test_circuit_breaker_open_half_open_close():
    br = CircuitBreaker(threshold=2, cooldown=0.05)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "closed"  # below threshold
    br.record_failure()
    assert br.state == "open" and not br.allow()
    time.sleep(0.06)
    assert br.allow()        # cooldown elapsed: the half-open trial
    assert not br.allow()    # exactly ONE trial at a time
    br.record_failure()      # trial failed -> re-open
    assert br.state == "open"
    time.sleep(0.06)
    assert br.allow()
    br.record_success()      # trial succeeded -> closed
    assert br.state == "closed" and br.allow()


def test_circuit_breaker_background_probe_closes_early():
    """With a probe, recovery is probe-driven: the breaker goes
    half-open as soon as the probe succeeds, without waiting out a long
    cooldown."""
    alive = threading.Event()
    br = CircuitBreaker(threshold=1, cooldown=60.0,
                        probe=alive.is_set, probe_interval=0.02)
    br.record_failure()
    assert br.state == "open"
    time.sleep(0.1)
    assert not br.allow()  # probe failing, cooldown far away
    alive.set()
    deadline = time.monotonic() + 2.0
    while br.state != "half_open" and time.monotonic() < deadline:
        time.sleep(0.01)
    assert br.state == "half_open"
    assert br.allow()
    br.record_success()
    assert br.state == "closed"


def test_circuit_breaker_probe_cadence_is_jittered():
    """The inter-probe sleep must be decorrelated-jittered, not a fixed
    cadence: after a supervised PS restart every client in the fleet
    opens its breaker at the same instant, and a fixed cadence lands
    all recovery probes on the reborn replica in synchronized waves.
    Fake clock: the injectable ``_sleep`` records delays instead of
    waiting, and the probe flips to success after a few rounds so the
    loop terminates deterministically."""
    rounds = []
    looked = threading.Event()

    def probe():
        # with no real sleeps the probe thread would reach half_open
        # before this test has looked at the open state
        looked.wait(5.0)
        rounds.append(1)
        return len(rounds) > 4  # fail 4 probes, then recover

    br = CircuitBreaker(threshold=1, cooldown=60.0,
                        probe=probe, probe_interval=0.25)
    sleeps = []
    br._sleep = sleeps.append  # fake clock: record, don't wait
    br.record_failure()
    assert br.state == "open"
    looked.set()
    deadline = time.monotonic() + 5.0
    while br.state != "half_open" and time.monotonic() < deadline:
        time.sleep(0.01)
    assert br.state == "half_open"
    assert len(sleeps) == 4  # one sleep per failed probe, none after
    for d in sleeps:
        assert br.probe_interval <= d <= 8 * br.probe_interval
    # jittered, not a fixed cadence: the draws must not all coincide
    assert len({round(d, 9) for d in sleeps}) > 1


def test_ps_client_fails_fast_when_open_and_recovers():
    """PsClient + breaker against a real PS service: kill the server ->
    the breaker opens after consecutive transport failures and later
    calls fail in microseconds (RpcCircuitOpen, no retry ladder);
    restart on the SAME port -> the TCP probe re-arms the trial and the
    client recovers transparently."""
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service.ps_service import PsClient, PsService

    svc = PsService(EmbeddingHolder(1000, 2))
    port = int(svc.addr.rsplit(":", 1)[1])
    client = PsClient(svc.addr, circuit_breaker=CircuitBreaker(
        threshold=1, cooldown=30.0, probe_interval=0.05,
        probe=__import__("persia_tpu.rpc", fromlist=["tcp_probe"])
        .tcp_probe(svc.addr, timeout=0.2)))
    client.client.max_retries = 0  # keep the failure ladder short
    client.client.retry_backoff = 0.01
    svc.server.serve_background()
    client.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
    client.register_optimizer({"type": "sgd", "lr": 0.1, "wd": 0.0})
    signs = np.arange(4, dtype=np.uint64)
    assert client.lookup(signs, DIM, True).shape == (4, DIM)

    svc.stop()
    client.client.close()  # drop the pooled conn: next call must redial
    with pytest.raises((ConnectionError, OSError)):
        client.lookup(signs, DIM, True)
    assert client.breaker.state == "open"
    t0 = time.perf_counter()
    with pytest.raises(RpcCircuitOpen):
        client.lookup(signs, DIM, True)
    assert time.perf_counter() - t0 < 0.05  # fail FAST: no wire, no retry

    svc2 = PsService(EmbeddingHolder(1000, 2), port=port)
    svc2.server.serve_background()
    try:
        svc2.holder.configure("bounded_uniform",
                              {"lower": -0.1, "upper": 0.1})
        svc2.holder.register_optimizer({"type": "sgd", "lr": 0.1,
                                        "wd": 0.0})
        deadline = time.monotonic() + 5.0
        out = None
        while time.monotonic() < deadline:
            try:
                out = client.lookup(signs, DIM, True)
                break
            except (ConnectionError, OSError):
                time.sleep(0.05)
        assert out is not None and out.shape == (4, DIM)
        assert client.breaker.state == "closed"
    finally:
        svc2.stop()


# --- staleness permit accounting -----------------------------------------


class _DeadWorker:
    """Every update fails with a transport-class error; recovery waits
    are instant so the retry ladder exhausts quickly."""

    def __init__(self, error=None):
        self.error = error or RpcConnectionLost(
            "synthetic permanent PS outage")
        self.updates = 0

    def wait_for_serving(self, timeout=None):
        pass

    def update_gradients(self, ref, grads, loss_scale=1.0):
        self.updates += 1
        raise self.error


def test_permanently_failed_update_releases_permit_as_lost_update():
    """ISSUE satellite: an update that exhausts every retry must
    RELEASE its staleness permit and count a lost_update — not poison
    the engine and wedge the trainer at the staleness bound."""
    from persia_tpu.pipeline import BackwardEngine

    w = _DeadWorker()
    sem = threading.Semaphore(2)
    sem.acquire()  # the permit the lookup took for this batch
    engine = BackwardEngine(w, num_workers=1, staleness_sem=sem)
    engine.submit(1, {"slot_a": np.zeros((4, DIM), np.float32)})
    engine.flush(timeout=30)  # completes: the loss is counted, not raised
    assert engine.lost_updates == 1
    assert w.updates == 5  # initial + 4 recoveries, all failed
    assert sem._value == 2  # permit released
    # the engine is NOT poisoned: later updates still flow
    sem.acquire()
    engine.submit(2, {"slot_a": np.zeros((4, DIM), np.float32)})
    engine.flush(timeout=30)
    assert engine.lost_updates == 2
    assert sem._value == 2
    engine.shutdown()


def test_application_rpc_error_is_fatal_not_lost_update():
    """A plain RpcError (handler bug, bad gradient shape) must surface
    to the trainer, NOT be silently counted as a lost update — only
    transport loss and shed deadlines are droppable."""
    from persia_tpu.pipeline import BackwardEngine

    w = _DeadWorker(error=RpcError("bad gradient shape"))
    sem = threading.Semaphore(2)
    sem.acquire()
    engine = BackwardEngine(w, num_workers=1, staleness_sem=sem)
    engine.submit(1, {"a": np.zeros((1, DIM), np.float32)})
    with pytest.raises(RpcError, match="bad gradient shape"):
        engine.flush(timeout=30)
    assert engine.lost_updates == 0
    assert sem._value == 2
    engine.shutdown()


def test_nested_transport_errors_retype_through_err_envelope():
    """A middle tier that loses ITS downstream hop reports the failure
    through a healthy connection; the err envelope re-types it so
    transport-aware callers (serving degradation, lost-update
    accounting) classify the nested outage correctly. Application
    errors stay plain RpcError."""

    def lost_downstream(p):
        raise ConnectionResetError("downstream PS hop died")

    def app_bug(p):
        raise ValueError("bad payload")

    srv = RpcServer()
    srv.register("relay", lost_downstream)
    srv.register("appfail", app_bug)
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr)
        with pytest.raises(RpcConnectionLost):
            cl.call("relay", b"")
        with pytest.raises(RpcError) as ei:
            cl.call("appfail", b"")
        assert not isinstance(ei.value, (ConnectionError, TimeoutError))
    finally:
        srv.stop()


def test_fatal_backward_error_still_propagates_and_frees_permit():
    """Programming errors (not transport) keep the old contract: flush
    raises; and a submit() rejected by the stored error releases the
    permit its batch held (the feeder-deadlock leak)."""
    from persia_tpu.pipeline import BackwardEngine

    class _Buggy:
        def update_gradients(self, ref, grads, loss_scale=1.0):
            raise ValueError("boom")

    sem = threading.Semaphore(2)
    sem.acquire()
    engine = BackwardEngine(_Buggy(), num_workers=1, staleness_sem=sem)
    engine.submit(1, {"a": np.zeros((1, DIM), np.float32)})
    with pytest.raises(ValueError, match="boom"):
        engine.flush(timeout=30)
    assert sem._value == 2  # the failed update's permit came back
    sem.acquire()
    with pytest.raises(ValueError, match="boom"):
        engine.submit(2, {"a": np.zeros((1, DIM), np.float32)})
    assert sem._value == 2  # the rejected batch's permit came back too
    engine.shutdown()


# --- liveness/readiness split --------------------------------------------


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_healthz_ready_split_during_restore():
    """/healthz stays 200 (alive — do not kill) while /healthz?ready=1
    turns 503 during Loading/restoring (do not route) — the supervisor
    vs k8s-probe split."""
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service.ps_service import PsService

    svc = PsService(EmbeddingHolder(1000, 2), http_port=0)
    svc.server.serve_background()
    try:
        base = f"http://{svc.http.addr}/healthz"
        svc.holder.register_optimizer({"type": "sgd", "lr": 0.1,
                                       "wd": 0.0})
        status, doc = _get(base + "?ready=1")
        assert status == 200 and doc["ready"] is True
        svc._set_status("Loading")
        status, doc = _get(base)           # liveness: still 200
        assert status == 200 and doc["ready"] is False
        status, doc = _get(base + "?ready=1")  # readiness: 503
        assert status == 503 and doc["model_manager_status"] == "Loading"
        svc._set_status("Idle")
        status, _ = _get(base + "?ready=1")
        assert status == 200
    finally:
        svc.stop()


# --- supervisor: crash recovery with checkpoint + inc replay -------------


def test_supervised_ps_kill_restart_restores_checkpoint_plus_inc(
        tmp_path, request):
    """Kill a supervised PS replica mid-training: the ServiceCtx
    supervisor restarts it with --initial-checkpoint + --replay-inc-dir,
    the worker re-resolves + re-arms, training resumes, and every row
    covered by the checkpoint + this replica's packets reads back
    EXACTLY from the restored store. The supervisor's flight recorder
    (postmortem_dir armed) must also leave a crash bundle for the
    killed replica built from its last /flight snapshot."""
    import yaml

    from persia_tpu import tracing
    from persia_tpu.checkpoint import iter_psd_entries
    from persia_tpu.service.helper import ServiceCtx
    from persia_tpu.service.ps_service import PsClient

    schema = EmbeddingSchema(
        slots_config=uniform_slots(["slot_a", "slot_b"], dim=DIM))
    ckpt = str(tmp_path / "ckpt")
    inc = str(tmp_path / "inc")
    pm_dir = str(tmp_path / "postmortems")
    gc_path = tmp_path / "gc.yml"
    yaml.safe_dump({"parameter_server": {
        "capacity": 100_000, "num_hashmap_internal_shards": 2,
        "enable_incremental_update": True, "incremental_buffer_size": 48,
        "incremental_dir": inc}}, gc_path.open("w"))

    rng = np.random.default_rng(0)
    # traced end to end so the killed replica's flight ring carries
    # rpc -> ps span chains for the bundle (enabled before any dial;
    # the finalizer restores the disabled default even on failure —
    # later tests assert the untraced wire)
    tracing.enable_tracing(True)
    request.addfinalizer(lambda: tracing.enable_tracing(False))
    with ServiceCtx(schema, n_workers=1, n_ps=2,
                    global_config_path=str(gc_path), supervise_ps=True,
                    ps_restore_dir=ckpt, ps_inc_dir=inc,
                    ps_probe_interval=0.25,
                    postmortem_dir=pm_dir, flight_interval=0.3,
                    env={"PERSIA_TRACING": "1"}) as svc:
        w = svc.remote_worker()
        w.configure_parameter_servers(
            "bounded_uniform", {"lower": -0.1, "upper": 0.1}, 1.0, 10.0)
        w.register_optimizer({"type": "sgd", "lr": 0.1, "wd": 0.0})

        def step(lo, hi):
            from persia_tpu.data.batch import IDTypeFeatureWithSingleID

            feats = [IDTypeFeatureWithSingleID(
                n, rng.integers(lo, hi, size=16, dtype=np.uint64))
                for n in ("slot_a", "slot_b")]
            ref, lk = w.lookup_direct_training(feats)
            w.update_gradients(
                ref, {k: np.ones_like(v.embeddings) for k, v in lk.items()})

        for _ in range(8):
            step(0, 4096)          # phase 1: durable rows
        w.dump(ckpt)
        for _ in range(4):
            step(0, 4096)          # a few packets past the checkpoint

        # let the flight recorder observe a POST-traffic snapshot of
        # the victim before the kill (the probe loop polls every 0.3s;
        # the first snapshot may predate the training steps above and
        # would make for a span-less bundle)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            doc = svc.flight_recorder.last("ps1")
            if doc is not None and doc.get("spans"):
                break
            time.sleep(0.05)
        assert svc.flight_recorder.last("ps1").get("spans")

        proc = svc.ps_proc(1)
        t_kill = time.monotonic()
        proc.kill()
        events = svc.wait_ps_recoveries(1, timeout=60)
        assert "failed" not in events[0]
        assert events[0]["t_detected"] - t_kill < 10.0

        # crash postmortem bundle: written before the respawn, from the
        # last observed flight snapshot
        bundle = events[0].get("postmortem")
        from tests.fleet_support import validate_postmortem

        health = validate_postmortem(bundle)
        assert health["model_manager_status"] == "Idle"
        import json

        with open(os.path.join(bundle, "reason.json")) as f:
            assert json.load(f)["service"] == "ps1"
        for _ in range(4):
            step(1 << 20, (1 << 20) + 4096)  # disjoint range post-kill
        assert w.staleness == 0

        # replay-order overlay of the durable artifacts == live store
        expected = {}
        for sign, _d, vec in iter_psd_entries(
                os.path.join(ckpt, "replica_1.psd")):
            if sign < (1 << 20):
                expected[sign] = vec
        for name in sorted(os.listdir(inc)):
            pth = os.path.join(inc, name, "1.inc")
            if name.startswith("inc_") and os.path.exists(pth):
                for sign, _d, vec in iter_psd_entries(pth):
                    if sign < (1 << 20):
                        expected[sign] = vec
        assert expected
        client = PsClient(svc.ps_addrs[1])
        for sign, vec in expected.items():
            got = client.get_entry(sign)
            assert got is not None, f"row {sign} lost in recovery"
            assert np.array_equal(got[1][:len(vec)], vec), \
                f"row {sign} not parity-exact after restore"


# --- serving degradation --------------------------------------------------


class _FailingLookupWorker:
    """Delegates to a real in-process worker; lookup RPCs fail on
    demand with a degradable (circuit-open) error."""

    def __init__(self, inner):
        self.inner = inner
        self.schema = inner.schema
        self.failing = False

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def lookup_signs(self, signs, dim):
        if self.failing:
            raise RpcCircuitOpen("synthetic: replica circuit open")
        return self.inner.lookup_signs(signs, dim)

    def lookup_direct(self, feats, training=False):
        if self.failing:
            raise RpcCircuitOpen("synthetic: replica circuit open")
        return self.inner.lookup_direct(feats, training=training)


def _serving_world():
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.worker.worker import EmbeddingWorker

    schema = EmbeddingSchema(slots_config=uniform_slots(
        ["slot_a", "slot_b"], dim=8))
    worker = EmbeddingWorker(schema, [EmbeddingHolder(100_000, 2)])
    worker.configure_parameter_servers(
        "bounded_uniform", {"lower": -0.1, "upper": 0.1}, 1.0, 10.0)
    worker.register_optimizer({"type": "sgd", "lr": 0.1, "wd": 0.0})
    return schema, worker


def _infer_request(rows, seed, vocab=512):
    from persia_tpu.data.batch import (
        IDTypeFeatureWithSingleID,
        NonIDTypeFeature,
        PersiaBatch,
    )

    rng = np.random.default_rng(seed)
    feats = [IDTypeFeatureWithSingleID(
        n, rng.integers(1, vocab, size=rows).astype(np.uint64))
        for n in ("slot_a", "slot_b")]
    dense = [NonIDTypeFeature(
        rng.normal(size=(rows, 5)).astype(np.float32))]
    return PersiaBatch(feats, non_id_type_features=dense,
                       requires_grad=False)


def test_serving_zero_vector_fallback_parity_on_unaffected_signs():
    """ISSUE satellite: with the embedding tier circuit-open, predict
    (a) still answers, (b) serves bit-identical outputs for requests
    whose signs are all in the hot-row cache (the unaffected signs),
    (c) counts the degraded lookups, and (d) never caches zero rows —
    full-fidelity answers resume immediately after recovery."""
    from persia_tpu.models import DNN
    from persia_tpu.serving import InferenceClient, InferenceServer, \
        build_state_template

    schema, inner = _serving_world()
    worker = _FailingLookupWorker(inner)
    # create the rows so cached predictions have real (nonzero) values
    req = _infer_request(8, seed=1)
    inner.lookup_direct(req.id_type_features, training=True)
    model = DNN()
    state = build_state_template(model, schema, 5)
    server = InferenceServer(model, state, schema, worker=worker,
                             cache_rows=10_000, cache_ttl_sec=300.0)
    server.serve_background()
    try:
        cl = InferenceClient(server.addr)
        healthy = cl.predict(req)           # primes the cache
        worker.failing = True
        degraded_same = cl.predict(req)     # all signs cached: unaffected
        np.testing.assert_array_equal(healthy, degraded_same)
        assert server._m_degraded.value == 0

        fresh = _infer_request(8, seed=2, vocab=100_000)  # cache misses
        pred = cl.predict(fresh)            # zero-vector fallback
        assert pred.shape[0] == 8
        assert server._m_degraded.value >= 1
        assert server._m_zero_rows.value >= 1

        worker.failing = False
        # create the fresh rows (training admits + initializes them);
        # because zero rows were NOT cached, the next predict refetches
        # and serves the real embeddings immediately
        inner.lookup_direct(fresh.id_type_features, training=True)
        degraded_total = server._m_degraded.value
        recovered = cl.predict(fresh)
        assert server._m_degraded.value == degraded_total
        assert not np.array_equal(pred, recovered)
    finally:
        server.stop()


def test_serving_uncached_path_degrades_whole_lookup():
    """Without a hot-row cache the fallback is coarser — the whole
    lookup zero-fills — but predict still answers and counts it."""
    from persia_tpu.models import DNN
    from persia_tpu.serving import InferenceClient, InferenceServer, \
        build_state_template

    schema, inner = _serving_world()
    worker = _FailingLookupWorker(inner)
    model = DNN()
    state = build_state_template(model, schema, 5)
    server = InferenceServer(model, state, schema, worker=worker)
    server.serve_background()
    try:
        cl = InferenceClient(server.addr)
        req = _infer_request(4, seed=3)
        cl.predict(req)
        worker.failing = True
        pred = cl.predict(req)
        assert pred.shape[0] == 4
        assert server._m_degraded.value == 1
        stats = cl.stats()
        assert stats["degraded_lookups"] == 1
        assert stats["zero_fallback_rows"] >= 1
    finally:
        server.stop()


def test_serving_degradation_opt_out():
    from persia_tpu.models import DNN
    from persia_tpu.serving import InferenceClient, InferenceServer, \
        build_state_template

    schema, inner = _serving_world()
    worker = _FailingLookupWorker(inner)
    worker.failing = True
    model = DNN()
    state = build_state_template(model, schema, 5)
    server = InferenceServer(model, state, schema, worker=worker,
                             degraded_fallback=False)
    server.serve_background()
    try:
        cl = InferenceClient(server.addr)
        with pytest.raises(RpcError):
            cl.predict(_infer_request(4, seed=4))
    finally:
        server.stop()


# --- reshard-protocol injection sites (PR 12 satellite) ----------------------


def test_reshard_fault_sites_targetable_by_spec():
    """PERSIA_FAULTS-style specs can target the migration protocol
    directly: a rule on ps.reshard.extract fails the donor's copy
    stream; a rule on ps.reshard.drain with frozen=True hits only the
    definitive cutover drain, not the replay rounds."""
    import numpy as np

    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.routing import RoutingTable
    from persia_tpu.service.ps_service import PsClient, PsService

    holder = EmbeddingHolder(capacity=10_000)
    svc = PsService(holder, port=0)
    svc.server.serve_background()
    client = PsClient(svc.addr, circuit_breaker=False)
    client.configure("bounded_uniform", {"lower": 0.0, "upper": 0.0},
                     admit_probability=1.0, weight_bound=1e9,
                     enable_weight_bound=False)
    client.register_optimizer({"type": "sgd", "lr": 1.0, "wd": 0.0})
    t = RoutingTable.uniform(1, slots_per_replica=4)
    client.lookup(np.arange(64, dtype=np.uint64), 8, True)
    try:
        faults.install("ps.reshard.extract:error")
        client.reshard_begin([0, 1], t.num_slots, epoch=2,
                             fence=(2, 0), mig_id="m")
        with pytest.raises(RpcError):
            client.reshard_extract(16, fence=(2, 0))
        faults.reset_faults()
        # frozen= kwarg filter: replay drains (frozen=False) pass, the
        # cutover drain (frozen=True) trips the rule
        faults.install("ps.reshard.drain:error@frozen=True")
        client.reshard_drain(fence=(2, 0))  # replay round: unharmed
        client.reshard_freeze(epoch=2, fence=(2, 0))
        with pytest.raises(RpcError):
            client.reshard_drain(fence=(2, 0))
        faults.reset_faults()
        client.reshard_finish(fence=(2, 0))
        # controller-side site: the driver's --die-at maps to a `die`
        # rule here; an `error` rule aborts the phase the same way
        faults.install("reshard.controller:error@state=freeze")
        from persia_tpu.reshard import ReshardController

        ctrl = ReshardController([client], t)
        with pytest.raises(faults.InjectedFault):
            ctrl._phase("freeze", donor=0)
        ctrl._phase("copy", donor=0)  # other states unharmed
    finally:
        faults.reset_faults()
        svc.stop()


def test_reshard_sites_zero_overhead_when_disarmed(monkeypatch):
    """The disabled path pin: with no rule armed (faults._active
    False), the reshard handlers and the controller's phase
    transitions must never reach faults.fire at all — the guard is a
    single module-global test."""
    import numpy as np

    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.reshard import ReshardController
    from persia_tpu.routing import RoutingTable
    from persia_tpu.service.ps_service import PsClient, PsService

    assert faults._active is False

    def boom(*a, **kw):  # noqa: ARG001
        raise AssertionError("faults.fire reached on the disabled path")

    monkeypatch.setattr(faults, "fire", boom)
    holder = EmbeddingHolder(capacity=1_000)
    svc = PsService(holder, port=0)
    svc.server.serve_background()
    try:
        client = PsClient(svc.addr, circuit_breaker=False)
        client.configure("bounded_uniform", {"lower": 0.0, "upper": 0.0},
                         admit_probability=1.0, weight_bound=1e9,
                         enable_weight_bound=False)
        client.register_optimizer({"type": "sgd", "lr": 1.0, "wd": 0.0})
        t = RoutingTable.uniform(1, slots_per_replica=4)
        client.lookup(np.arange(16, dtype=np.uint64), 8, True)
        client.reshard_begin([0], t.num_slots, epoch=2, fence=(2, 0),
                             mig_id="m")
        client.reshard_extract(8, fence=(2, 0))
        client.reshard_drain(fence=(2, 0))
        client.reshard_freeze(epoch=2, fence=(2, 0))
        client.reshard_status()
        client.reshard_finish(fence=(2, 0))
        ReshardController([client], t)._phase("copy", donor=0)
    finally:
        svc.stop()
