"""The device-mode step's account as the benchmark reads it: the five
readers on a ring and a registry with nothing in them, in the traced
rehearsal of a DLRM and a sequence cell, the manifest's five entries,
``tracing.device_time_by_scope`` over the recorded trace against
``trace_reduce.reduce``, and ``tools/step_scopes.py`` end to end."""

import gzip
import importlib.util
import json
import os

import pytest

import manifest
import program_gauges
import step_scopes
import trace_reduce
from persia_tpu import metrics, tracing
from test_harness import BENCH_DIR, ROOT, cells, rehearse

NEW = {"dispatch_ms": ("ms", "program_span", "ctx / cached tier",
                       "samples_per_s"),
       "dispatch_buffers": ("count", "program_counter", "ctx / cached tier",
                            "samples_per_s"),
       "trainer_init_s": ("s", "program_counter", "entry / ctx", "setup_s"),
       "first_call_s": ("s", "program_counter", "entry / ctx", "setup_s"),
       "first_call_compile_s": ("s", "program_counter", "entry / ctx",
                                "setup_s")}
GAUGES = {"trainer_init_s": "device_mode_init_seconds",
          "first_call_s": "device_mode_first_call_seconds",
          "first_call_compile_s": "device_mode_first_call_compile_seconds"}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH_DIR, "layer_metrics",
                                       f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def ring(monkeypatch):
    fresh = tracing.TraceCollector(capacity=4)
    monkeypatch.setattr(tracing, "_collector", fresh)
    return fresh


@pytest.fixture
def registry(monkeypatch):
    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "default_registry", lambda: fresh)
    return fresh


def _dispatch(ms, profiled=True, **tags):
    s = tracing.Span("trainer/dispatch", 1, tracing._rand64(), 0,
                     tags=tags or None, profiled=profiled)
    s.dur_ns = int(ms * 1e6)
    return s


def test_the_manifest_has_the_five_entries_over_the_accepted_cells():
    man = manifest.Manifest(ROOT)
    assert man.validate()
    accepted = cells(held_back=False)
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    assert [m["name"] for m in man.doc["per_layer"]][-5:] == list(NEW)
    for name, (unit, source, layer, moves) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"]) == (unit, source, layer, moves, "lower")
        assert m["workloads"] == accepted
        assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics",
                                           f"{name}.py"))
    layers = {m["layer"] for m in man.doc["per_layer"][:-5]}
    assert {layer for _, _, layer, _ in NEW.values()} <= layers


@pytest.mark.parametrize("name", list(NEW))
def test_a_reader_finds_nothing_in_an_empty_ring_and_registry(
        name, ring, registry):
    assert reader(name).read(None) is None


def test_a_gauge_reads_none_until_it_is_set(registry):
    for metric, gauge in GAUGES.items():
        assert reader(metric).read(None) is None
        registry.gauge(gauge)       # there, never set
        assert reader(metric).read(None) is None
        registry.gauge(gauge).set(1.25)
        assert reader(metric).read(None) == 1.25
    # asking registers nothing
    assert program_gauges.value("device_mode_no_such_gauge") is None
    assert "no_such_gauge" not in registry.render()


def test_dispatch_readers_read_the_profiled_spans_and_their_tags(ring):
    ms, buffers = reader("dispatch_ms"), reader("dispatch_buffers")
    ring.add(_dispatch(90.0, profiled=False, args=1, results=1))
    assert ms.read(None) is None and buffers.read(None) is None
    ring.add(_dispatch(2.0, args=118, results=91, compiled=False))
    ring.add(_dispatch(4.0, args=118, results=91, compiled=False))
    assert ms.read(None) == pytest.approx(3.0)
    assert buffers.read(None) == pytest.approx(209.0)
    # a TrainCtx's dispatch span has no buffer counts: a time, no count
    ring.clear()
    ring.add(_dispatch(5.0, compiled=False))
    assert ms.read(None) == pytest.approx(5.0)
    assert buffers.read(None) is None


def test_a_ring_that_dropped_a_span_gives_neither_reading(ring):
    for _ in range(5):
        ring.add(_dispatch(2.0, args=3, results=2))
    assert ring.dropped_total == 1
    assert reader("dispatch_ms").read(None) is None
    assert reader("dispatch_buffers").read(None) is None


def one_cell_of(*placements):
    man = manifest.Manifest(ROOT)
    found = {}
    for name in cells(held_back=False):
        found.setdefault(man.cell(name)[1]["placement"], name)
    return [found[p] for p in placements]


@pytest.mark.parametrize("cell", one_cell_of("device", "device_seq"))
def test_a_traced_rehearsal_reads_the_five(cell):
    tracing.default_collector().clear()
    line = rehearse(cell, 1)
    assert set(NEW) <= set(line["read"])
    spans = [s for s in tracing.default_collector().recent()
             if s.name == "trainer/dispatch"]
    assert spans and all(s.profiled for s in spans)
    assert len({(s.tags["args"], s.tags["results"]) for s in spans}) == 1
    assert not any(s.tags["compiled"] for s in spans)
    for gauge in GAUGES.values():
        assert program_gauges.value(gauge) > 0


def test_an_untraced_rehearsal_records_no_dispatch_span():
    tracing.default_collector().clear()
    line = rehearse(one_cell_of("device")[0], 0)
    assert not set(NEW) & set(line["read"])
    assert len(tracing.default_collector()) == 0


def test_scopes_over_the_recorded_trace_sum_to_its_busy_time():
    """Two steps of the mlperf cell on a v5e (2 488 operations). The
    table is made from the events' own names, a scope an operation
    family, so every event is matched; the self times then sum to the
    union of the busy intervals, which is what ``reduce`` calls
    ``busy_s``."""
    with gzip.open(os.path.join(BENCH_DIR, "tests",
                                "recorded_trace.json.gz"), "rt") as f:
        recorded = json.load(f)
    dev = recorded["trace"]["devices"][0]
    assert len(dev["ops"]) == 2488
    table = {}
    for name, _, _ in dev["ops"]:
        instruction = tracing._instruction_of(name)
        family = instruction.split(".")[0]
        table[instruction] = (f"step/{family}", "scatter" in name)
    reduced = trace_reduce.reduce(recorded["trace"],
                                  recorded["table_shapes"])
    got = tracing.device_time_by_scope(dev["ops"], dev["modules"], table)
    assert got["total_s"] == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert got["total_s"] == pytest.approx(recorded["expect"]["busy_s"])
    assert got["steps"] == pytest.approx(reduced["steps"]) == 2.0
    assert got["unmatched_s"] == 0 and got["unscoped_s"] == 0
    assert sum(f + b for _, f, b in got["scopes"]) == pytest.approx(
        got["total_s"])
    assert got["scopes"][0][0] == "fusion"
    whole = tracing.device_time_by_scope(dev["ops"], dev["modules"], table,
                                         depth=0)
    assert whole["scopes"][0][0] == "step/fusion"
    # half the table: what it lacks is counted and named, never dropped
    half = dict(list(table.items())[::2])
    partly = tracing.device_time_by_scope(dev["ops"], dev["modules"], half)
    assert partly["total_s"] == pytest.approx(got["total_s"])
    assert partly["unmatched_s"] > 0 and partly["unmatched"]
    assert (sum(f + b for _, f, b in partly["scopes"])
            + partly["unmatched_s"]) == pytest.approx(got["total_s"])


def test_step_scopes_runs_a_cell_and_reads_its_step_s_table(tmp_path,
                                                             capsys):
    out = tmp_path / "scopes.json"
    rc = step_scopes.main(
        ["--out", str(out), "--workload", one_cell_of("device")[0],
         "--seed", "7", "--seconds", "1.5", "--rehearse", "--roll-up",
         "tables_gather,tower,row_update,optimizer"])
    assert rc == 0
    doc = json.loads(out.read_text())
    # a rehearsal's trace has no device plane: instructions, no time
    assert {"tables_gather", "tower", "row_update", "optimizer"} <= set(
        doc["instructions_by_scope"])
    assert "by_scope" not in doc and doc["instructions"] > 1000
    assert "instructions by scope" in capsys.readouterr().out
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace"))


def test_step_scopes_renders_a_report():
    report = {"steps": 2.0, "total_s": 0.2, "unscoped_s": 0.02,
              "unmatched_s": 0.01, "unmatched": [["stray.9", 0.01]],
              "scopes": [["experts", 0.05, 0.1], ["head", 0.02, 0.0]]}
    text = step_scopes.render(report, device_step_ms=99.0)
    assert "total 100.000 (device_step_ms 99.000, +1.01 %)" in text
    rows = {line.split()[0]: line.split()[1:] for line in text.splitlines()}
    assert rows["experts"] == ["25.000", "50.000", "75.000", "75.00%"]
    assert "not in the table: stray.9 5.000" in text
    assert step_scopes.rolled_up(
        {"a": ("m/tower/mlp/dense", True), "b": ("other", False)},
        ["tower"]) == {"a": ("m/tower", True), "b": ("other", False)}
