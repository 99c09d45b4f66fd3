"""Observability stack tests: cross-tier trace propagation over real
RPC (tagged + legacy peers, including the out-of-order multiplexed
path), the HTTP sidecar's /metrics + /healthz + /trace endpoints,
Prometheus exposition escaping and render-vs-observe consistency, and
Chrome-trace export validity."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from persia_tpu import tracing
from persia_tpu.metrics import MetricsRegistry
from persia_tpu.rpc import RpcClient, RpcServer


@pytest.fixture
def traced():
    """Enable tracing for one test, with a clean collector, and restore
    the disabled default afterwards (other tests assert the untraced
    wire)."""
    tracing.enable_tracing(True)
    tracing.default_collector().clear()
    yield tracing.default_collector()
    tracing.enable_tracing(False)


def _spans_named(collector, name):
    return [s for s in collector.recent() if s.name == name]


# --- trace propagation over RPC ------------------------------------------


def test_trace_propagates_over_tagged_rpc(traced):
    srv = RpcServer(concurrent_streams=4)
    srv.register("echo", lambda p: p)
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr)
        with tracing.span("client/root") as root:
            assert cl.call("echo", b"x") == b"x"
            futs = [cl.call_future("echo", bytes([i])) for i in range(4)]
            assert [f.result() for f in futs] == [bytes([i])
                                                 for i in range(4)]
        spans = _spans_named(traced, "rpc/echo")
        assert len(spans) == 5
        assert all(s.trace_id == root.trace_id for s in spans)
        assert all(s.parent_id == root.span_id for s in spans)
    finally:
        srv.stop()


def test_trace_parentage_across_ooo_multiplexed_path(traced):
    """Slow requests answered OUT OF ORDER from pool threads must still
    parent to the issuing span — the context rides the envelope, not
    the connection state."""
    done_order = []

    def handler(p):
        if p == b"slow":
            time.sleep(0.15)
        done_order.append(bytes(p))
        return p

    srv = RpcServer(concurrent_streams=8)
    srv.register("work", handler)
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr)
        with tracing.span("client/burst") as root:
            payloads = [b"slow", b"a", b"b", b"c"]
            assert cl.call_many("work", payloads, window=4) == payloads
        # the slow request completed last server-side even though it was
        # sent first: the burst really did execute out of order
        assert done_order[-1] == b"slow"
        spans = _spans_named(traced, "rpc/work")
        assert len(spans) == 4
        assert {s.trace_id for s in spans} == {root.trace_id}
        assert {s.parent_id for s in spans} == {root.span_id}
    finally:
        srv.stop()


def test_legacy_peer_negotiates_down(traced):
    """A peer without the __trace__ handler refuses the probe; calls
    still work and no server spans appear."""
    srv = RpcServer(enable_tags=False, enable_trace=False)
    srv.register("echo", lambda p: p)
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr)
        with tracing.span("client/legacy"):
            assert cl.call("echo", b"y") == b"y"
        assert not _spans_named(traced, "rpc/echo")
    finally:
        srv.stop()


def test_disabled_tracing_sends_no_probe():
    """With tracing off (the default) the dial sequence is byte-
    identical to the legacy wire: no __trace__ probe, no envelope slot.
    The server's served-request counter observes exactly the calls."""
    assert not tracing.tracing_enabled()
    srv = RpcServer()
    srv.register("echo", lambda p: p)
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr, enable_tags=False)
        assert cl.call("echo", b"z") == b"z"
        assert srv.health()["served_rpcs"] == 1  # no probe traffic
        cl.close()

        tracing.enable_tracing(True)
        try:
            cl2 = RpcClient(srv.addr, enable_tags=False)
            assert cl2.call("echo", b"z") == b"z"
            # probe + call — the extra request only exists when enabled
            assert srv.health()["served_rpcs"] == 3
        finally:
            tracing.enable_tracing(False)
    finally:
        srv.stop()


def test_server_span_records_handler_error(traced):
    srv = RpcServer()

    def boom(p):
        raise ValueError("nope")

    srv.register("boom", boom)
    srv.serve_background()
    try:
        cl = RpcClient(srv.addr)
        with tracing.span("client/err"):
            from persia_tpu.rpc import RpcError

            with pytest.raises(RpcError):
                cl.call("boom")
        (sp,) = _spans_named(traced, "rpc/boom")
        assert "ValueError" in sp.tags["error"]
    finally:
        srv.stop()


# --- cross-tier: worker + PS services over real sockets -------------------


def test_worker_ps_cycle_shares_one_trace(traced):
    """One traced worker cycle (put/lookup/update) over two real PS
    RPC services: worker stage spans and both replicas' handler spans
    share the root's trace_id with correct parentage."""
    from persia_tpu.config import EmbeddingSchema, SlotConfig
    from persia_tpu.data.batch import IDTypeFeatureWithSingleID
    from persia_tpu.ps.native import make_holder
    from persia_tpu.service.ps_service import PsClient, PsService
    from persia_tpu.worker.worker import EmbeddingWorker

    schema = EmbeddingSchema(slots_config={
        f"s{i}": SlotConfig(name=f"s{i}", dim=8 * (1 + i % 2))
        for i in range(6)
    })
    services = [PsService(make_holder(10_000, 4)) for _ in range(2)]
    for s in services:
        s.server.serve_background()
    clients = [PsClient(s.addr) for s in services]
    worker = EmbeddingWorker(schema, clients)
    try:
        worker.configure_parameter_servers(
            "bounded_uniform", {"lower": -0.01, "upper": 0.01}, 1.0, 10.0)
        worker.register_optimizer({
            "type": "adagrad", "lr": 0.02, "initial_accumulator_value": 0.1,
            "g_square_momentum": 1.0, "vectorwise_shared": False,
        })
        traced.clear()  # configure traffic is not the cycle under test
        rng = np.random.default_rng(0)
        feats = [
            IDTypeFeatureWithSingleID(
                f"s{i}", rng.integers(0, 1 << 30, size=64, dtype=np.uint64))
            for i in range(6)
        ]
        with tracing.span("trainer/step", root=True) as root:
            ref = worker.put_batch(feats)
            lk = worker.lookup(ref)
            worker.update_gradients(
                ref, {k: v.embeddings for k, v in lk.items()})

        spans = traced.recent()
        by_id = {s.span_id: s for s in spans}
        lookups = [s for s in spans if s.name == "rpc/lookup"]
        updates = [s for s in spans if s.name == "rpc/update_gradients"]
        assert lookups and updates
        for s in spans:
            assert s.trace_id == root.trace_id, s.name
        # parent chain: rpc/lookup -> worker/ps_lookup(_mux) ->
        # worker/rpc -> trainer/step
        for s in lookups:
            parent = by_id[s.parent_id]
            assert parent.name in ("worker/ps_lookup", "worker/ps_lookup_mux")
            grand = by_id[parent.parent_id]
            assert grand.name == "worker/rpc"
            assert by_id[grand.parent_id].name == "trainer/step"
        for s in updates:
            parent = by_id[s.parent_id]
            assert parent.name == "worker/ps_update"
        stage_names = {s.name for s in spans}
        assert {"worker/preprocess", "worker/rpc",
                "worker/postprocess"} <= stage_names
    finally:
        worker.close()
        for c in clients:
            c.client.close()
        for s in services:
            s.stop()


# --- HTTP sidecar ---------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.read().decode()


def test_sidecar_metrics_healthz_trace(traced):
    from persia_tpu.obs_http import ObservabilityServer

    reg = MetricsRegistry()
    reg.counter("obs_test_requests_total", {"svc": "t"}).inc(3)
    with tracing.span("sidecar/span"):
        pass
    sidecar = ObservabilityServer(
        registry=reg, health_fn=lambda: {"queue_depth": 7},
        service="testsvc").start()
    try:
        metrics = _get(f"http://{sidecar.addr}/metrics")
        assert 'obs_test_requests_total{svc="t"} 3.0' in metrics
        health = json.loads(_get(f"http://{sidecar.addr}/healthz"))
        assert health["status"] == "ok"
        assert health["service"] == "testsvc"
        assert health["queue_depth"] == 7
        assert health["uptime_sec"] >= 0
        from persia_tpu.version import __version__

        assert health["version"] == __version__  # fleet skew detection
        trace = json.loads(_get(f"http://{sidecar.addr}/trace?n=10"))
        names = [e["name"] for e in trace["traceEvents"]
                 if e["ph"] == "X"]
        assert "sidecar/span" in names
        assert trace["otherData"]["spans_dropped_total"] == 0
        raw = json.loads(_get(f"http://{sidecar.addr}/trace?n=5&format=raw"))
        assert any(s["name"] == "sidecar/span" for s in raw["spans"])
        assert raw["dropped_total"] == 0
        flight = json.loads(_get(f"http://{sidecar.addr}/flight"))
        assert flight["health"]["queue_depth"] == 7
        assert 'obs_test_requests_total{svc="t"} 3.0' in flight["metrics"]
        assert any(s["name"] == "sidecar/span" for s in flight["spans"])
        assert isinstance(flight["faults"], list)
    finally:
        sidecar.stop()


def test_trace_ring_counts_drops(traced):
    """A full bounded ring counts evictions instead of discarding
    silently, and the sidecar's /trace responses carry the count."""
    from persia_tpu.obs_http import ObservabilityServer

    coll = tracing.TraceCollector(capacity=8)
    for i in range(20):
        with tracing.span(f"drop/span{i}"):
            pass
        coll.add(tracing.default_collector().recent(1)[0])
    assert coll.dropped_total == 12
    sidecar = ObservabilityServer(collector=coll, service="dropper").start()
    try:
        raw = json.loads(
            _get(f"http://{sidecar.addr}/trace?format=raw"))
        assert raw["dropped_total"] == 12
        assert len(raw["spans"]) == 8
        chrome = json.loads(_get(f"http://{sidecar.addr}/trace"))
        assert chrome["otherData"]["spans_dropped_total"] == 12
    finally:
        sidecar.stop()


def test_ps_service_sidecar_health():
    from persia_tpu.ps.native import make_holder
    from persia_tpu.service.ps_service import PsClient, PsService

    svc = PsService(make_holder(1000, 2), http_port=0)
    svc.server.serve_background()
    try:
        cl = PsClient(svc.addr)
        cl.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
        cl.register_optimizer({
            "type": "adagrad", "lr": 0.02, "initial_accumulator_value": 0.1,
            "g_square_momentum": 1.0, "vectorwise_shared": False,
        })
        cl.lookup(np.arange(1, 9, dtype=np.uint64), 8, True)
        health = json.loads(_get(f"http://{svc.http.addr}/healthz"))
        assert health["holder_entries"] == 8
        assert health["model_manager_status"] == "Idle"
        assert health["served_rpcs"] >= 2
        assert health["inflight_rpcs"] == 0
        assert health["last_activity_age_sec"] < 60
        # /metrics answers valid exposition on the same sidecar
        assert _get(f"http://{svc.http.addr}/metrics").endswith("\n")
        cl.client.close()
    finally:
        svc.stop()


def test_worker_service_sidecar_health():
    from persia_tpu.config import EmbeddingSchema, uniform_slots
    from persia_tpu.data.batch import IDTypeFeatureWithSingleID
    from persia_tpu.ps.native import make_holder
    from persia_tpu.service.worker_service import WorkerService
    from persia_tpu.worker.worker import EmbeddingWorker

    schema = EmbeddingSchema(slots_config=uniform_slots(["a"], dim=8))
    worker = EmbeddingWorker(schema, [make_holder(1000, 2)])
    svc = WorkerService(worker, http_port=0)
    svc.server.serve_background()
    try:
        worker.configure_parameter_servers(
            "bounded_uniform", {"lower": -0.1, "upper": 0.1}, 1.0, 10.0)
        worker.register_optimizer({
            "type": "adagrad", "lr": 0.02, "initial_accumulator_value": 0.1,
            "g_square_momentum": 1.0, "vectorwise_shared": False,
        })
        ref = worker.put_batch([IDTypeFeatureWithSingleID(
            "a", np.arange(1, 5, dtype=np.uint64))])
        worker.lookup(ref)  # training: takes a staleness permit
        health = json.loads(_get(f"http://{svc.http.addr}/healthz"))
        assert health["forward_buffer_depth"] == 0
        assert health["post_forward_buffer_depth"] == 1
        assert health["staleness"] == 1
        assert health["ps_replicas"] == 1
    finally:
        worker.close()
        svc.stop()


# --- metrics satellites ---------------------------------------------------


def test_exposition_escapes_label_values():
    reg = MetricsRegistry()
    reg.counter("esc_total", {"addr": 'a"b\\c\nd'}).inc()
    out = reg.render()
    (line,) = [l for l in out.splitlines() if l.startswith("esc_total")]
    assert line == 'esc_total{addr="a\\"b\\\\c\\nd"} 1.0'
    # one metric line stays ONE line (no exposition injection): the
    # family's TYPE comment plus exactly one sample line
    esc_lines = [l for l in out.splitlines() if "esc" in l]
    assert esc_lines == ["# TYPE esc_total counter", line]
    # and the escaped value survives a parse round trip
    from persia_tpu.metrics import parse_exposition

    samples, families = parse_exposition(out)
    d = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
    assert d[("esc_total", (("addr", 'a"b\\c\nd'),))] == 1.0
    assert families["esc_total"]["type"] == "counter"


def test_exposition_type_help_parse_back():
    """Satellite: render() emits # TYPE (and # HELP where available)
    for every family — counter, gauge, histogram — and the output
    parses back sample-exact."""
    reg = MetricsRegistry()
    reg.counter("pb_reqs_total", {"svc": "a"},
                help_text="requests served").inc(5)
    reg.counter("pb_reqs_total", {"svc": "b"}).inc(2)
    reg.gauge("pb_depth").set(3)
    h = reg.histogram("pb_lat_sec")
    h.observe(0.002)
    h.observe(7.0)
    out = reg.render()
    lines = out.splitlines()
    assert "# TYPE pb_reqs_total counter" in lines
    assert "# HELP pb_reqs_total requests served" in lines
    assert "# TYPE pb_depth gauge" in lines
    assert "# TYPE pb_lat_sec histogram" in lines
    # TYPE once per family, not per series
    assert lines.count("# TYPE pb_reqs_total counter") == 1
    from persia_tpu.metrics import parse_exposition

    samples, families = parse_exposition(out)
    d = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
    assert d[("pb_reqs_total", (("svc", "a"),))] == 5.0
    assert d[("pb_reqs_total", (("svc", "b"),))] == 2.0
    assert d[("pb_depth", ())] == 3.0
    assert d[("pb_lat_sec_count", ())] == 2.0
    assert d[("pb_lat_sec_sum", ())] == 7.002
    assert d[("pb_lat_sec_bucket", (("le", "+Inf"),))] == 2.0
    assert families["pb_lat_sec"]["type"] == "histogram"
    assert families["pb_reqs_total"]["help"] == "requests served"


def test_render_vs_observe_race_is_consistent():
    """Concurrent observes must never produce a torn render: the +Inf
    cumulative bucket must equal _count in EVERY rendered snapshot."""
    reg = MetricsRegistry()
    hist = reg.histogram("race_sec")
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            hist.observe(0.0001 * (i % 100))
            i += 1

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(50):
            out = reg.render()
            inf_line = [l for l in out.splitlines()
                        if l.startswith("race_sec_bucket")
                        and 'le="+Inf"' in l][0]
            count_line = [l for l in out.splitlines()
                          if l.startswith("race_sec_count")][0]
            assert inf_line.rsplit(" ", 1)[1] == count_line.rsplit(" ", 1)[1]
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_gauge_add_dec_threadsafe():
    reg = MetricsRegistry()
    g = reg.gauge("depth")

    def work():
        for _ in range(2000):
            g.add(1)
            g.dec(1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert g.value == 0.0


def test_push_loop_stop_event():
    reg = MetricsRegistry()
    reg.counter("push_total").inc()
    # closed port: pushes fail quietly; the loop must still honor stop
    thread, stop = reg.push_loop("job", interval_sec=0.05,
                                 gateway_addr="127.0.0.1:9")
    assert thread.is_alive()
    stop.set()
    thread.join(timeout=5)
    assert not thread.is_alive()


# --- export + profiler ----------------------------------------------------


def test_chrome_trace_export_validity(traced, tmp_path):
    with tracing.span("outer", root=True):
        with tracing.span("inner", k="v"):
            pass
    path = tmp_path / "trace.json"
    with open(path, "w") as f:
        json.dump(tracing.chrome_trace(), f)
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    for e in xs:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and e["tid"]
        int(e["args"]["trace_id"], 16)  # valid hex ids
        int(e["args"]["span_id"], 16)
    inner = next(e for e in xs if e["name"] == "inner")
    outer = next(e for e in xs if e["name"] == "outer")
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert inner["args"]["k"] == "v"
    # process_name metadata names the track
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)


def test_pipeline_batch_carries_trace(traced):
    """ForwardEngine opens one root per batch and hands the context to
    the LookedUpBatch; the queue-depth gauges return to zero."""
    from persia_tpu.config import EmbeddingSchema, uniform_slots
    from persia_tpu.data.batch import (
        IDTypeFeatureWithSingleID,
        PersiaBatch,
    )
    from persia_tpu.metrics import default_registry
    from persia_tpu.pipeline import ForwardEngine
    from persia_tpu.ps.native import make_holder
    from persia_tpu.worker.worker import EmbeddingWorker

    schema = EmbeddingSchema(slots_config=uniform_slots(["a"], dim=8))
    worker = EmbeddingWorker(schema, [make_holder(1000, 2)])
    worker.configure_parameter_servers(
        "bounded_uniform", {"lower": -0.1, "upper": 0.1}, 1.0, 10.0)
    worker.register_optimizer({
        "type": "adagrad", "lr": 0.02, "initial_accumulator_value": 0.1,
        "g_square_momentum": 1.0, "vectorwise_shared": False,
    })

    class DummyCtx:
        pass

    ctx = DummyCtx()
    ctx.worker = worker
    engine = ForwardEngine(ctx, num_workers=2)
    rng = np.random.default_rng(0)
    batches = [
        PersiaBatch([IDTypeFeatureWithSingleID(
            "a", rng.integers(1, 1 << 20, size=16, dtype=np.uint64))],
            requires_grad=False)
        for _ in range(4)
    ]
    try:
        out = list(engine.run(iter(batches)))
        assert len(out) == 4
        traces = {lb.trace for lb in out}
        assert None not in traces
        assert len(traces) == 4  # one fresh root per batch
        roots = _spans_named(traced, "pipeline/lookup")
        assert {s.ctx for s in roots} == traces
        reg = default_registry()
        assert reg.gauge("pipeline_lookup_queue_depth").value == 0
        assert reg.gauge("pipeline_ready_queue_depth").value == 0
    finally:
        engine.shutdown()
        worker.close()


def test_step_profiler_window(tmp_path, monkeypatch):
    from persia_tpu.tracing import StepProfiler, profiler_from_env

    calls = []

    class FakeProfiler:
        @staticmethod
        def start_trace(logdir):
            calls.append(("start", logdir))

        @staticmethod
        def stop_trace():
            calls.append(("stop", None))

    import jax

    monkeypatch.setattr(jax, "profiler", FakeProfiler)
    p = StepProfiler(str(tmp_path), start_step=3, num_steps=2)
    for i in range(1, 8):
        p.on_step(i)
    assert calls == [("start", str(tmp_path)), ("stop", None)]
    p.close()  # idempotent after the window closed
    assert calls == [("start", str(tmp_path)), ("stop", None)]

    monkeypatch.setenv("PERSIA_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("PERSIA_PROFILE_START_STEP", "1")
    monkeypatch.setenv("PERSIA_PROFILE_NUM_STEPS", "1")
    env_p = profiler_from_env()
    assert env_p is not None and env_p.start_step == 1
    monkeypatch.delenv("PERSIA_PROFILE_DIR")
    assert profiler_from_env() is None
