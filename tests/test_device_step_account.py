"""The device-mode step's own account: ``tracing.scope_table`` over a
compiled step's text, ``tracing.device_time_by_scope`` over event lists
made by hand, what ``DeviceStep`` records a call (span, buffer counts,
gauges, recompilations), the profiler window's ``device_scopes.json``,
and the one compile watch a process has."""

import contextlib
import json
import logging
import os
import re
import subprocess
import sys

import jax
import optax
import pytest

from persia_tpu import metrics, tracing
from persia_tpu.models import DLRM
from persia_tpu.parallel.device_mode import (
    DeviceModeModel,
    DeviceStep,
    criteo_like_specs,
    make_device_mode_trainer,
    synthetic_device_batch,
)
from persia_tpu.parallel.mesh import make_mesh

SPECS = criteo_like_specs(num_slots=3, vocab=64, dim=8)


def _value(name):
    return metrics.default_registry().gauge(name).value


@pytest.fixture
def ring():
    tracing.default_collector().clear()
    yield tracing.default_collector()
    tracing.enable_tracing(False)
    tracing.default_collector().clear()


@contextlib.contextmanager
def _logged(name):
    """The records a program logger emits (its loggers do not propagate
    to the root, so ``caplog`` sees nothing of them)."""
    records = []
    handler = logging.Handler(logging.DEBUG)
    handler.emit = records.append
    log = logging.getLogger(name)
    level = log.level
    log.addHandler(handler)
    log.setLevel(logging.DEBUG)
    try:
        yield records
    finally:
        log.removeHandler(handler)
        log.setLevel(level)


def _build(batch=16, pooling="sum"):
    non_id, ids, label = synthetic_device_batch(batch, 13, SPECS)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    params, opt_state, step = make_device_mode_trainer(
        DeviceModeModel(slot_specs=SPECS, tower=DLRM(embedding_dim=8),
                        pooling=pooling),
        optax.adagrad(0.05), mesh, non_id, ids)
    return mesh, params, opt_state, step, (non_id, ids, label)


@pytest.fixture(scope="module")
def compiled_text():
    mesh, params, opt_state, step, batch = _build()
    with mesh:
        return step.lower(params, opt_state, *batch).compile().as_text()


# --- scope_table ----------------------------------------------------------


def test_scope_table_names_every_instruction_of_every_computation(
        compiled_text):
    table = tracing.scope_table(compiled_text)
    named = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", compiled_text,
                       re.M)
    assert len(named) > 500 and set(named) == set(table)
    # more than the entry computation's: a fusion's own instructions too
    computations = [c for c in compiled_text.split("\n\n") if " = " in c]
    assert len(computations) > 10
    for path, backward in table.values():
        assert isinstance(path, str) and isinstance(backward, bool)
        assert "(" not in path and "jit" not in path.split("/")


def test_scope_table_finds_the_programs_scopes(compiled_text):
    table = tracing.scope_table(compiled_text)
    paths = {path for path, _ in table.values()}
    assert {"tables_gather", "optimizer", "optimizer/row_update"} <= paths
    assert any(p.split("/")[-1] == "tower" for p in paths)
    # the touched rows' work lies inside the optimizer's scope only
    assert not any("row_update" in p and not p.startswith("optimizer/")
                   for p in paths)
    # the pooling's transpose is the backward under the gather's name
    backward = {p for p, b in table.values()
                if b and p.endswith("tables_gather")}
    forward = {p for p, b in table.values()
               if not b and p.endswith("tables_gather")}
    assert backward and "tables_gather" in forward
    assert all("bag_slot_" in p for p in backward)
    # the tower runs both ways, the optimizer forward only
    assert {b for p, b in table.values()
            if p.split("/")[-1].startswith("Dense_")} == {False, True}
    assert {b for p, b in table.values()
            if p.startswith("optimizer")} == {False}
    # an instruction without metadata has no scope
    assert ("", False) in table.values()


@pytest.mark.parametrize("op_name,expect", [
    ("jit(step)/jit(main)/tower/layer_3/experts/experts_grouped/dot_general",
     ("tower/layer_3/experts/experts_grouped", False)),
    ("jit(step)/transpose(jvp(Model))/tower/jvp(Model)/tower/checkpoint/"
     "rematted_computation/layer_1/experts/while/body/jit(gmm)/mul",
     ("Model/tower/layer_1/experts", True)),
    ("jit(step)/jvp(Model)/tower/layer_0/hyper_maps/while/cond/lt",
     ("Model/tower/layer_0/hyper_maps", False)),
    ("jit(step)/transpose(jvp())/div", ("", True)),
    ("jit(step)/optimizer/jit(_where)/select_n", ("optimizer", False)),
    ("jit(step)/tables_gather/jit(_take)", ("tables_gather", False)),
    ("jit(step)/a/b/mul;jit(step)/c/add", ("a/b", False)),
    ("params['tower']['w']", ("", False)),
])
def test_scope_path_peels_what_jax_wraps_around_a_scope(op_name, expect):
    line = f'  %x.1 = f32[2]{{0}} add(%a, %b), metadata={{op_name="{op_name}"}}'
    assert tracing.scope_table(line) == {"x.1": expect}


def test_scope_table_reads_root_and_bare_names():
    text = ("ENTRY %main {\n"
            "  %p = f32[] parameter(0)\n"
            '  fusion.2 = f32[] fusion(%p), metadata={op_name="jit(f)/s/mul"}\n'
            '  ROOT %t-done.3 = (f32[]) tuple(fusion.2)\n}')
    assert tracing.scope_table(text) == {
        "p": ("", False), "fusion.2": ("s", False), "t-done.3": ("", False)}


# --- device_time_by_scope -------------------------------------------------

# one step of 100 ns: a while that wraps two body events (one of them a
# fusion with an event nested inside it), a forward event before it, an
# event without a scope after it, one the table lacks, and an idle gap
OPS = [
    ["%gather.1 = f32[8]{0} gather(...)", 0.0, 10.0],
    ["%while.7 = (s32[]) while(...), body=%b", 10.0, 50.0],
    ["%fusion.3 = f32[8]{0} fusion(...), kind=kLoop", 12.0, 20.0],
    ["%inner.4 = f32[8]{0} custom-call(...)", 15.0, 5.0],
    ["%dot.5 = f32[8]{0} dot(...)", 35.0, 20.0],
    ["%copy.6 = f32[8]{0} copy(...)", 70.0, 10.0],
    ["%stray.9 = f32[8]{0} add(...)", 90.0, 10.0],
]
MODULES = [["jit_step(1)", 0.0, 100.0], ["jit_step(1)", 100.0, 100.0],
           ["jit_other(2)", 200.0, 3.0]]
TABLE = {"gather.1": ("tables_gather", False),
         "while.7": ("m/tower/experts", True),
         "fusion.3": ("m/tower/experts", True),
         "inner.4": ("m/tower/experts/kernel", True),
         "dot.5": ("m/tower/experts", False),
         "copy.6": ("", False)}


def _busy_ns(ops):
    merged = []
    for start, end in sorted((s, s + d) for _, s, d in ops):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged)


def test_self_times_by_hand():
    # while: 50 less its body's union (12..32 and 35..55); fusion: 20
    # less the 5 inside it
    assert tracing._self_times(OPS) == [10.0, 10.0, 15.0, 5.0, 20.0, 10.0,
                                        10.0]


def test_device_time_by_scope_by_hand():
    got = tracing.device_time_by_scope(OPS, MODULES, TABLE)
    assert got["steps"] == pytest.approx(2.0)
    assert got["total_s"] == pytest.approx(80e-9)
    assert got["total_s"] == pytest.approx(_busy_ns(OPS) / 1e9)
    assert got["unscoped_s"] == pytest.approx(10e-9)
    assert got["unscoped"] == [["copy", pytest.approx(10e-9)]]
    assert got["unmatched_s"] == pytest.approx(10e-9)
    assert got["unmatched"] == [["stray.9", pytest.approx(10e-9)]]
    assert got["scopes"] == [
        ["experts", pytest.approx(20e-9), pytest.approx(25e-9)],
        ["tables_gather", pytest.approx(10e-9), 0.0],
        ["kernel", 0.0, pytest.approx(5e-9)]]
    assert (sum(f + b for _, f, b in got["scopes"]) + got["unscoped_s"]
            + got["unmatched_s"]) == pytest.approx(got["total_s"])


def test_another_program_s_events_are_not_looked_up_in_this_table():
    """Instruction names repeat from program to program: ``gather.1`` of
    the small program that runs between two steps is not this step's."""
    ops = OPS + [["%gather.1 = f32[8]{0} gather(...)", 200.5, 2.0]]
    got = tracing.device_time_by_scope(ops, MODULES, TABLE)
    assert got["unmatched"] == [["stray.9", pytest.approx(10e-9)],
                                ["jit_other(2)", pytest.approx(2e-9)]]
    assert got["scopes"][1] == ["tables_gather", pytest.approx(10e-9), 0.0]
    assert got["total_s"] == pytest.approx(82e-9)


def test_depth_cuts_paths_to_their_innermost_names():
    by = {d: [row[0] for row in tracing.device_time_by_scope(
        OPS, MODULES, TABLE, depth=d)["scopes"]] for d in (None, 1, 2, 0)}
    assert by[None] == by[1] == ["experts", "tables_gather", "kernel"]
    assert by[2] == ["tower/experts", "tables_gather", "experts/kernel"]
    assert by[0] == ["m/tower/experts", "tables_gather",
                     "m/tower/experts/kernel"]


def test_self_times_sum_to_the_busy_intervals_whatever_the_overlaps():
    # events that overlap without nesting (an async copy beside compute),
    # equal starts, a zero-length event, any order
    ops = [["a", 0.0, 10.0], ["b", 5.0, 10.0], ["c", 5.0, 2.0],
           ["d", 30.0, 0.0], ["e", 40.0, 5.0], ["f", 12.0, 30.0],
           ["g", 41.0, 1.0]]
    for events in (ops, ops[::-1]):
        own = tracing._self_times(events)
        assert all(x >= 0 for x in own)
        assert sum(own) == pytest.approx(_busy_ns(events))
    got = tracing.device_time_by_scope(ops, [], {})
    assert got["steps"] == 0.0 and got["scopes"] == []
    assert got["unmatched_s"] == pytest.approx(got["total_s"])
    assert tracing.device_time_by_scope([], [], {})["total_s"] == 0.0


# --- DeviceStep -----------------------------------------------------------


def test_a_call_is_one_dispatch_span_with_its_buffers_counted(ring):
    mesh, params, opt_state, step, batch = _build()
    tracing.enable_tracing(True)
    leaves_in = len(jax.tree_util.tree_leaves((params, opt_state, batch)))
    with mesh:
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, *batch)
    leaves_out = len(jax.tree_util.tree_leaves((params, opt_state, loss)))
    spans = [s for s in ring.recent() if s.name == "trainer/dispatch"]
    assert len(spans) == 3
    assert [s.tags for s in spans] == [
        {"args": leaves_in, "results": leaves_out, "compiled": c}
        for c in (True, False, False)]
    # 3 tables and 12 tower leaves with an accumulator each, Adagrad's
    # empty rest, 1 + 3 + 1 batch leaves; out: the state and a loss
    assert (leaves_in, leaves_out) == (35, 31)
    assert all(not s.profiled for s in spans)


def test_with_both_switches_off_the_span_is_the_null_span(ring,
                                                          monkeypatch):
    mesh, params, opt_state, step, batch = _build()
    assert not tracing.tracing_enabled() and not tracing.profiler_live()
    opened = []
    real = tracing.span
    monkeypatch.setattr(tracing, "span", lambda *a, **kw: opened.append(
        real(*a, **kw)) or opened[-1])
    with mesh:
        for _ in range(2):
            params, opt_state, _ = step(params, opt_state, *batch)
    assert len(opened) == 2 and all(s is tracing._NULL_SPAN for s in opened)
    assert len(ring) == 0


def test_lower_and_the_rest_pass_through_to_the_jitted_function():
    mesh, params, opt_state, step, batch = _build()
    assert isinstance(step, DeviceStep)
    with mesh:
        lowered = step.lower(params, opt_state, *batch)
    assert "tables_gather" in lowered.as_text(debug_info=True)
    assert step.lower == step._jitted.lower
    assert step.__wrapped__ is step._jitted.__wrapped__
    with pytest.raises(AttributeError):
        step.no_such_attribute


def test_the_dense_step_is_accounted_for_too(ring):
    non_id, ids, label = synthetic_device_batch(16, 13, SPECS)
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    params, opt_state, step = make_device_mode_trainer(
        DeviceModeModel(slot_specs=SPECS, tower=DLRM(embedding_dim=8)),
        optax.adam(1e-3), mesh, non_id, ids)
    assert isinstance(step, DeviceStep)
    tracing.enable_tracing(True)
    with mesh:
        step(params, opt_state, non_id, ids, label)
    (span,) = [s for s in ring.recent() if s.name == "trainer/dispatch"]
    assert span.tags["compiled"] is True and span.tags["args"] > 35


def test_build_and_first_call_set_their_gauges():
    for name in ("device_mode_init_seconds",
                 "device_mode_first_call_seconds",
                 "device_mode_first_call_compile_seconds"):
        metrics.default_registry().gauge(name).set(-1.0)
    mesh, params, opt_state, step, batch = _build()
    init = _value("device_mode_init_seconds")
    assert 0 < init < 120
    assert _value("device_mode_first_call_seconds") == -1.0
    with mesh:
        params, opt_state, _ = step(params, opt_state, *batch)
    first = _value("device_mode_first_call_seconds")
    inside = _value("device_mode_first_call_compile_seconds")
    # the compiler's seconds lie inside the call's (a cache load too)
    assert 0 < inside <= first < 120
    with mesh:
        step(params, opt_state, *batch)
    assert _value("device_mode_first_call_seconds") == first
    assert _value("device_mode_init_seconds") == init
    text = metrics.default_registry().render()
    for name in ("device_mode_init_seconds", "jax_backend_compiles_total",
                 "jax_backend_compile_seconds_total",
                 "jax_compile_cache_hits_total",
                 "device_mode_step_recompiles_total"):
        assert f"\n{name} " in text


def test_a_second_batch_size_is_counted_tagged_and_logged(ring):
    mesh, params, opt_state, step, batch = _build(batch=16)
    wider = synthetic_device_batch(32, 13, SPECS)
    counter = metrics.default_registry().counter(
        "device_mode_step_recompiles_total")
    before = counter.value
    tracing.enable_tracing(True)
    with _logged("persia_tpu.parallel.device_mode") as records, mesh:
        params, opt_state, _ = step(params, opt_state, *batch)
        params, opt_state, _ = step(params, opt_state, *batch)
        assert counter.value == before
        params, opt_state, _ = step(params, opt_state, *wider)
        params, opt_state, _ = step(params, opt_state, *wider)
    assert counter.value == before + 1
    tags = [s.tags["compiled"] for s in ring.recent()
            if s.name == "trainer/dispatch"]
    assert tags == [True, False, True, False]
    (record,) = [r for r in records if r.levelno == logging.WARNING]
    said = record.getMessage()
    assert "call 2" in said
    assert "float32[16,13] -> float32[32,13]" in said
    assert "['slot_0']: int32[16,1] -> int32[32,1]" in said
    # what did not change is not listed: no parameter, no state leaf
    assert "tower" not in said and "sum_of_squares" not in said


def test_scopes_of_its_own_compiled_step():
    mesh, params, opt_state, step, batch = _build()
    with pytest.raises(RuntimeError):
        step.scopes()
    with mesh:
        params, opt_state, _ = step(params, opt_state, *batch)
    # the state may be gone: only shapes were kept
    for leaf in jax.tree_util.tree_leaves((params, opt_state)):
        leaf.delete()
    table = step.scopes()
    assert step.scopes() is table
    paths = {path for path, _ in table.values()}
    assert {"tables_gather", "optimizer/row_update"} <= paths
    for leaf in jax.tree_util.tree_leaves(step._avals):
        assert isinstance(leaf, jax.ShapeDtypeStruct)


# --- the operator's profiler window ----------------------------------------


def test_device_mode_honours_the_profile_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("PERSIA_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("PERSIA_PROFILE_START_STEP", "1")
    monkeypatch.setenv("PERSIA_PROFILE_NUM_STEPS", "2")
    mesh, params, opt_state, step, batch = _build()
    profiler = step._profiler
    assert profiler is not None and profiler.scopes == step.scopes
    seen = []
    with _logged("persia_tpu.tracing") as records, mesh:
        for _ in range(5):
            params, opt_state, _ = step(params, opt_state, *batch)
            seen.append(profiler.active)
    # on from before call 1 until the boundary before call 3
    assert seen == [False, True, True, False, False]
    xplanes = [f for _, _, files in os.walk(tmp_path) for f in files
               if f.endswith(".xplane.pb")]
    assert len(xplanes) == 1
    # a CPU session has no device plane: a warning, no exception, no file
    assert any("no device time by scope" in r.getMessage()
               and r.levelno == logging.WARNING for r in records)
    assert not any("device_scopes.json" in files
                   for _, _, files in os.walk(tmp_path))
    step.close()    # nothing left open


def test_without_the_profile_dir_the_step_has_no_profiler(monkeypatch):
    monkeypatch.delenv("PERSIA_PROFILE_DIR", raising=False)
    assert _build()[3]._profiler is None


def test_step_profiler_writes_device_scopes_beside_the_xplane(
        tmp_path, monkeypatch):
    asked = []

    def scopes():
        asked.append(1)
        return TABLE

    monkeypatch.setattr(tracing, "load_device_events",
                        lambda path: (OPS, MODULES))
    p = tracing.StepProfiler(str(tmp_path), start_step=0, num_steps=1,
                             scopes=scopes)
    with _logged("persia_tpu.tracing") as records:
        p.on_step(0)
        jax.block_until_ready(jax.numpy.ones(4) + 1)
        p.on_step(1)
    assert not p.active and asked == [1]
    (out,) = [os.path.join(base, f) for base, _, files in os.walk(tmp_path)
              for f in files if f == "device_scopes.json"]
    assert any(f.endswith(".xplane.pb") for f in os.listdir(
        os.path.dirname(out)))
    with open(out) as f:
        report = json.load(f)
    assert report["steps"] == pytest.approx(2.0)
    assert report["scopes"][0] == ["experts", pytest.approx(20e-9),
                                   pytest.approx(25e-9)]
    assert any("experts 0.00" in r.getMessage() for r in records)


def test_step_profiler_without_scopes_reports_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(tracing, "load_device_events", lambda path: 1 / 0)
    p = tracing.StepProfiler(str(tmp_path), start_step=0, num_steps=1)
    p.on_step(0)
    p.close()
    assert not any("device_scopes.json" in files
                   for _, _, files in os.walk(tmp_path))


def test_load_device_events_refuses_a_trace_without_a_device_plane(
        tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jax.numpy.ones(4) + 1)
    jax.profiler.stop_trace()
    (xplane,) = [os.path.join(base, f)
                 for base, _, files in os.walk(tmp_path)
                 for f in files if f.endswith(".xplane.pb")]
    with pytest.raises(LookupError):
        tracing.load_device_events(xplane)


# --- the compile watch ----------------------------------------------------


def test_compile_watch_registers_once_however_often_it_is_called(
        monkeypatch):
    from jax import monitoring

    first = tracing.compile_watch()
    registered = []
    monkeypatch.setattr(monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    monkeypatch.setattr(monitoring, "register_event_listener",
                        registered.append)
    assert tracing.compile_watch() is first
    _build()
    assert tracing.compile_watch() is first and registered == []


def test_compile_watch_counts_a_compilation_once():
    watch = tracing.compile_watch()
    reg = metrics.default_registry()
    count = reg.counter("jax_backend_compiles_total")
    seconds = reg.counter("jax_backend_compile_seconds_total")
    x = jax.numpy.arange(7.0)
    before = (watch.compiles, watch.seconds, count.value, seconds.value)
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    assert watch.compiles == before[0] + 1
    assert count.value == before[2] + 1
    grown = watch.seconds - before[1]
    assert grown > 0
    assert seconds.value - before[3] == pytest.approx(grown)


def test_a_service_process_registers_no_compile_watch():
    """The PS and worker services import ``tracing`` and no JAX: nothing
    of the account is built there, and the scope functions work without
    it."""
    code = (
        "import sys; from persia_tpu import tracing; "
        "import persia_tpu.service.ps_service, "
        "persia_tpu.service.worker_service; "
        "assert tracing._watch is None; "
        "t = tracing.scope_table('  %a.1 = f32[] add(), "
        "metadata={op_name=\"jit(f)/s/add\"}'); "
        "r = tracing.device_time_by_scope([['%a.1 = f32[] add()', 0., 5.]],"
        " [], t); assert r['scopes'] == [['s', 5e-9, 0.0]], r; "
        "assert not [m for m in sys.modules if m == 'jax' "
        "or m.startswith('jax.')], 'jax imported'")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=root), timeout=120)
