"""The program's spans on the profiler's clock: ``profiler_live`` against
a real ``jax.profiler`` session on the CPU, a span's way into the xplane
and the ring, what stays untouched with both switches off (the null
span, the RPC bytes, a JAX-free child process), the spans of one trainer
step in each mode, and the scope names inside the jitted steps."""

import os
import socket
import struct
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import optax
import pytest

from persia_tpu import tracing
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.rpc import RpcClient, RpcServer
from test_device_cache import (
    _bag_batches,
    _bag_schema,
    _make_ctx,
    _schema,
    _zipf_batches,
)

TRAINER_SPANS = ("trainer/lookup_direct", "trainer/prep_inputs",
                 "trainer/place_batch", "trainer/dispatch",
                 "trainer/grad_submit")
CACHE_SPANS = ("cache/map", "cache/miss_import", "worker/rows_with_state",
               "trainer/dispatch", "cache/finish")


@pytest.fixture
def ring():
    tracing.default_collector().clear()
    yield tracing.default_collector()
    tracing.enable_tracing(False)
    tracing.default_collector().clear()


@pytest.fixture
def profiler(tmp_path):
    """A live ``jax.profiler`` session without the Python tracer; the
    test stops it itself where it reads the xplane."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    state = {"live": True}

    def stop():
        if state["live"]:
            state["live"] = False
            jax.profiler.stop_trace()
        return tmp_path

    yield stop
    stop()


def host_events(trace_dir, name):
    """(plane, stats) of every event called ``name`` on a ``/host:``
    plane of the xplane written under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = []
    for base, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                data = ProfileData.from_file(os.path.join(base, f))
                for plane in data.planes:
                    if not plane.name.startswith("/host:"):
                        continue
                    for line in plane.lines:
                        found += [(plane.name, dict(e.stats))
                                  for e in line.events if e.name == name]
    return found


# --- the switch -----------------------------------------------------------


def test_profiler_live_follows_a_real_session(tmp_path):
    assert tracing.profiler_live() is False
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.profiler_live() is True
    finally:
        jax.profiler.stop_trace()
    assert tracing.profiler_live() is False


def test_a_span_under_the_profiler_is_in_the_xplane_and_the_ring(
        ring, profiler):
    assert not tracing.tracing_enabled()
    with tracing.span("trainer/outer", root=True) as outer:
        with tracing.span("cache/probe", rows=7, kind="miss") as sp:
            sp.tag(bucket=64)
    trace_dir = profiler()
    with tracing.span("after/stop"):
        pass
    recorded = {s.name: s for s in ring.recent()}
    assert set(recorded) == {"trainer/outer", "cache/probe"}
    assert recorded["cache/probe"].parent_id == outer.span_id
    for s in recorded.values():
        assert s.profiled and s.to_dict()["profiled"] is True
    events = host_events(trace_dir, "cache/probe")
    assert len(events) == 1 and len(host_events(trace_dir,
                                                "trainer/outer")) == 1
    plane, stats = events[0]
    assert plane.startswith("/host:")
    assert (stats["rows"], stats["kind"], stats["bucket"]) == (7, "miss", 64)


def test_tracing_alone_marks_nothing_profiled(ring):
    tracing.enable_tracing(True)
    with tracing.span("plain/span"):
        pass
    (s,) = ring.recent()
    assert s.profiled is False and s.to_dict()["profiled"] is False


def test_a_profiler_session_propagates_no_context(ring, profiler):
    with tracing.span("local/only") as sp:
        assert sp.ctx is not None
        assert tracing.current_context() is None
        with tracing.span("fanned/out", ctx=tracing.current_context()) as c:
            assert c is tracing._NULL_SPAN


# --- off stays off --------------------------------------------------------


def test_both_switches_off_is_the_shared_null_span(ring):
    assert not tracing.tracing_enabled() and not tracing.profiler_live()
    sp = tracing.span("trainer/place_batch", leaves=3)
    assert sp is tracing._NULL_SPAN and sp.ctx is None
    with sp as inside:
        assert inside.tag(x=1) is sp
    assert len(ring) == 0


class _RecordingProxy:
    """Forwards one TCP connection to ``target`` and keeps what the
    client sent."""

    def __init__(self, target):
        self.sent = b""
        self._lsock = socket.socket()
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(1)
        self.addr = "127.0.0.1:%d" % self._lsock.getsockname()[1]
        host, port = target.rsplit(":", 1)
        self._target = (host, int(port))
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        down, _ = self._lsock.accept()
        up = socket.create_connection(self._target)

        def pump_back():
            while True:
                data = up.recv(65536)
                if not data:
                    break
                down.sendall(data)

        back = threading.Thread(target=pump_back, daemon=True)
        back.start()
        while True:
            data = down.recv(65536)
            if not data:
                break
            self.sent += data
            up.sendall(data)
        up.shutdown(socket.SHUT_RDWR)  # wakes pump_back's recv
        back.join(timeout=5)
        up.close()
        down.close()

    def close(self):
        self._lsock.close()
        self._thread.join(timeout=5)
        assert not self._thread.is_alive()


def _request_bytes(srv):
    """What one dial and one ``echo`` call put on the wire."""
    proxy = _RecordingProxy(srv.addr)
    cl = RpcClient(proxy.addr, enable_tags=False)
    try:
        with tracing.span("client/call"):
            assert cl.call("echo", b"z") == b"z"
    finally:
        cl.close()
        proxy.close()
    return proxy.sent


def test_a_profiler_session_adds_no_byte_to_a_request(ring, profiler):
    srv = RpcServer()
    srv.register("echo", lambda p: p)
    srv.serve_background()
    try:
        live = _request_bytes(srv)
        assert [s.name for s in ring.recent()] == ["client/call"]
        profiler()
        off = _request_bytes(srv)
    finally:
        srv.stop()
    assert live == off
    # the untraced wire as it always was: one frame, [method, nbytes]
    env = msgpack.packb(["echo", 1], use_bin_type=True)
    assert off.endswith(
        struct.pack("<IBH", 3 + len(env) + 1, 0, len(env)) + env + b"z")
    assert b"__trace__" not in off


def test_tracing_imports_no_jax_in_a_fresh_interpreter():
    code = ("import sys; from persia_tpu import tracing; "
            "assert tracing.profiler_live() is False; "
            "assert tracing.span('x') is tracing._NULL_SPAN; "
            "assert not [m for m in sys.modules if m == 'jax' "
            "or m.startswith('jax.')], 'jax imported'")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root)
    env.pop("PERSIA_TRACING", None)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


# --- the spans of one step ------------------------------------------------


def _holder_worker(schema):
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.worker.worker import EmbeddingWorker

    return EmbeddingWorker(schema, [EmbeddingHolder(100_000, 2),
                                    EmbeddingHolder(100_000, 2)])


def _step_spans(ring, step):
    """The spans of trainer step ``step``'s trace, by name, and the step's
    own span."""
    (root,) = [s for s in ring.recent() if s.name == "trainer/train_step"
               and s.tags["step"] == step]
    by_name = {}
    for s in ring.recent():
        if s.trace_id == root.trace_id:
            by_name.setdefault(s.name, []).append(s)
    return root, by_name


def _leads_to(span, root, spans):
    by_id = {s.span_id: s for s in spans}
    while span.parent_id and span.span_id != root.span_id:
        span = by_id[span.parent_id]
    return span.span_id == root.span_id


def test_hybrid_step_yields_each_trainer_span_once(ring):
    from persia_tpu.parallel.mesh import make_mesh

    tracing.enable_tracing(True)
    ctx = _make_ctx(_holder_worker(_schema()), mesh=make_mesh((8, 1)))
    with ctx:
        for b in _zipf_batches(2, 64):
            ctx.train_step(b)
    first, _ = _step_spans(ring, 1)
    root, spans = _step_spans(ring, 2)
    assert first.trace_id != root.trace_id
    everything = [s for group in spans.values() for s in group]
    for name in TRAINER_SPANS:
        (s,) = spans[name]
        assert s.parent_id == root.span_id, name
        assert _leads_to(s, root, everything)
    tags = spans["trainer/place_batch"][0].tags
    assert tags["leaves"] == 2 and tags["bytes"] == 64 * 13 * 4 + 64 * 4
    # two leaves: a packed transfer and its unpack would be no fewer calls
    assert tags["transfers"] == 2 and tags["packed_leaves"] == 0
    assert spans["trainer/dispatch"][0].tags == {"compiled": False}
    (built,) = [s for s in ring.recent() if s.name == "trainer/dispatch"
                and s.trace_id == first.trace_id]
    assert built.tags == {"compiled": True}


@pytest.mark.parametrize("bags", [False, True], ids=["single_id", "bags"])
def test_cached_step_yields_each_cache_span_once(ring, bags):
    tracing.enable_tracing(True)
    schema = _bag_schema() if bags else _schema()
    batches = _bag_batches(2, 64) if bags else _zipf_batches(2, 64)
    ctx = _make_ctx(_holder_worker(schema), cache_capacity=4096,
                    schema=schema)
    with ctx:
        for b in batches:
            ctx.train_step(b)
    root, spans = _step_spans(ring, 2)
    everything = [s for group in spans.values() for s in group]
    for name in CACHE_SPANS:
        (s,) = spans[name]
        assert _leads_to(s, root, everything), name
        if name != "worker/rows_with_state":
            assert s.parent_id == root.span_id, name
    (rows,) = spans["worker/rows_with_state"]
    (imported,) = spans["cache/miss_import"]
    assert rows.parent_id == imported.span_id
    mapped = spans["cache/map"][0].tags
    assert 0 < mapped["misses"] <= mapped["unique"] <= mapped["signs"]
    assert imported.tags["rows"] == mapped["misses"] == rows.tags["n"]
    assert imported.tags["bucket"] >= imported.tags["rows"]
    assert rows.tags["replicas"] == 2
    assert spans["cache/finish"][0].tags == {"evicted": 0}
    assert "trainer/lookup_direct" not in spans


def test_eviction_write_back_joins_the_evicting_step_s_trace(ring):
    tracing.enable_tracing(True)
    ctx = _make_ctx(_holder_worker(_schema()), cache_capacity=280)
    with ctx:
        for b in _zipf_batches(10, 64):
            ctx.train_step(b)
        ctx.flush_device_cache()
    finishes = {s.span_id: s for s in ring.recent()
                if s.name == "cache/finish" and s.tags["evicted"]}
    backs = [s for s in ring.recent() if s.name == "cache/writeback"]
    assert finishes and len(backs) == len(finishes)
    for s in backs:
        assert s.tid == "device-cache-flush"
        parent = finishes[s.parent_id]
        assert s.trace_id == parent.trace_id
        assert 0 <= s.tags["rows"] <= parent.tags["evicted"]


def test_rows_with_state_moves_the_lookup_rpc_histogram():
    worker = _holder_worker(_schema())
    worker.configure_parameter_servers(
        "bounded_uniform", {"lower": -0.1, "upper": 0.1}, 1.0, 10.0)
    worker.register_optimizer(Adagrad(lr=0.05).config)
    try:
        before = worker._t_rpc.count
        vals, state = worker.lookup_rows_with_state(
            np.arange(1, 33, dtype=np.uint64), 8, default_state=0.1)
        assert vals.shape == state.shape == (32, 8)
        assert worker._t_rpc.count == before + 1
    finally:
        worker.close()


# --- names on the device side ---------------------------------------------


def _scopes_in(lowered, names):
    text = lowered.as_text(debug_info=True)
    return {n for n in names if f"{n}/" in text or f"{n})" in text}


def test_device_mode_step_carries_its_scopes():
    from persia_tpu.models import DLRM
    from persia_tpu.parallel.device_mode import (
        DeviceModeModel,
        criteo_like_specs,
        make_device_mode_trainer,
        synthetic_device_batch,
    )
    from persia_tpu.parallel.mesh import make_mesh

    specs = criteo_like_specs(num_slots=3, vocab=64, dim=8)
    non_id, ids, label = synthetic_device_batch(16, 13, specs)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    params, opt_state, step = make_device_mode_trainer(
        DeviceModeModel(slot_specs=specs, tower=DLRM(embedding_dim=8)),
        optax.adagrad(0.05), mesh, non_id, ids)
    names = ("tables_gather", "tower", "optimizer", "row_update")
    with mesh:
        lowered = step.lower(params, opt_state, non_id, ids, label)
    assert _scopes_in(lowered, names) == set(names)
    lines = lowered.as_text(debug_info=True).splitlines()
    # the backward under the gather's name is the pooling's alone: the
    # step differentiates with respect to the gathered rows, so no
    # scatter into a table is anybody's transpose ...
    backward = [line for line in lines
                if "transpose(" in line and "/tables_gather/" in line]
    assert backward and not any("scatter" in line for line in backward)
    # ... and the touched rows' own work sits inside the optimizer's
    assert any("/optimizer/row_update/" in line for line in lines)
    assert not any("/row_update/" in line and "/optimizer/" not in line
                   for line in lines)


@pytest.mark.parametrize("bags", [False, True], ids=["single_id", "bags"])
def test_cached_steps_carry_their_scopes(bags):
    from persia_tpu.models import DLRM
    from persia_tpu.parallel.cached_train import (
        init_cache_arrays,
        make_cached_bag_train_step,
        make_cached_train_step,
    )
    from persia_tpu.parallel.train import create_train_state

    batch, slots, dim, cap, pad = 16, 3, 8, 64, 64
    model, opt = DLRM(embedding_dim=dim), optax.adagrad(0.05)
    non_id = [jnp.zeros((batch, 13), jnp.float32)]
    state = create_train_state(
        model, opt, jax.random.key(0), non_id,
        [np.zeros((batch, dim), np.float32)] * slots)
    vals, acc = init_cache_arrays(cap, dim, 0.1)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    cold = (i32(pad), jnp.zeros((pad, dim)), jnp.zeros((pad, dim)))
    label = jnp.zeros((batch, 1), jnp.float32)
    kw = dict(lr=0.05, eps=1e-8, g_square_momentum=1.0, capacity=cap)
    if bags:
        step = make_cached_bag_train_step(model, opt, slots, dim, **kw)
        lowered = step.lower(state, vals, acc, non_id, i32(pad), i32(pad),
                             jnp.ones((batch, slots)), *cold, i32(pad),
                             i32(pad), label)
    else:
        step = make_cached_train_step(model, opt, slots, dim, **kw)
        lowered = step.lower(state, vals, acc, non_id, i32(batch, slots),
                             *cold, i32(batch * slots), i32(batch * slots),
                             label)
    names = ("cache_import", "cache_gather", "tower", "dense_update",
             "row_adagrad")
    assert _scopes_in(lowered, names) == set(names)
