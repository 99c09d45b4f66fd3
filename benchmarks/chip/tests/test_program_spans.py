"""The layer metrics that read the program's own spans: the reader's
arithmetic on a ring filled by hand, ``place_batch_ms`` in a device cell's
traced rehearsal, and the three readers kept for the held-back cached
cell, put into a copy of the manifest beside its entries."""

import json
import os

import pytest

import manifest
import program_spans
from persia_tpu import tracing
from test_harness import BENCH_DIR, HELD_BACK, ROOT, cells, rehearse

CACHED_READERS = {"cache_map_ms": "cached tier",
                  "cache_miss_import_ms": "worker + PS tier",
                  "cache_dispatch_ms": "ctx / cached tier"}


@pytest.fixture
def ring(monkeypatch):
    """A ring of this test's own: ``dropped_total`` counts from the
    process's start, and the other tests' rehearsals share the process."""
    fresh = tracing.TraceCollector(capacity=4)
    monkeypatch.setattr(tracing, "_collector", fresh)
    return fresh


def _finished(name, ms, profiled=True):
    s = tracing.Span(name, 1, tracing._rand64(), 0, profiled=profiled)
    s.dur_ns = int(ms * 1e6)
    return s


def test_mean_is_over_the_profiled_spans_of_that_name(ring):
    assert program_spans.mean_ms("trainer/place_batch") is None
    ring.add(_finished("trainer/place_batch", 2.0))
    ring.add(_finished("trainer/place_batch", 4.0))
    ring.add(_finished("trainer/place_batch", 90.0, profiled=False))
    ring.add(_finished("trainer/dispatch", 1.0))
    assert program_spans.mean_ms("trainer/place_batch") == pytest.approx(3.0)
    assert program_spans.mean_ms("trainer/dispatch") == pytest.approx(1.0)
    assert program_spans.mean_ms("cache/map") is None


def test_a_ring_that_dropped_a_span_gives_no_reading(ring):
    for _ in range(5):
        ring.add(_finished("trainer/place_batch", 2.0))
    assert ring.dropped_total == 1
    assert program_spans.mean_ms("trainer/place_batch") is None


def test_a_program_without_profiled_spans_gives_no_reading(ring):
    """The parent's spans have no ``profiled`` mark."""
    class Old:
        name, dur_ns = "trainer/place_batch", 2_000_000

    ring.add(Old())
    assert program_spans.mean_ms("trainer/place_batch") is None


def cells_that_report(metric):
    """The manifest's own cells that list the metric, as it says."""
    man = manifest.Manifest(ROOT)
    return [c for c in cells(held_back=False)
            if metric in {m["name"] for m in man.metrics_of(c, "per_layer")}]


@pytest.mark.parametrize("cell", cells_that_report("place_batch_ms"))
def test_a_device_cell_s_traced_rehearsal_reads_place_batch_ms(cell):
    tracing.default_collector().clear()
    assert "place_batch_ms" in rehearse(cell, 1)["read"]
    spans = [s for s in tracing.default_collector().recent()
             if s.name == "trainer/place_batch"]
    assert spans and all(s.profiled for s in spans)
    assert all(s.tags["leaves"] == 28 and s.tags["bytes"] > 0
               for s in spans)


def test_an_untraced_rehearsal_records_no_span():
    tracing.default_collector().clear()
    rehearse(cells(held_back=False)[0], 0)
    assert len(tracing.default_collector()) == 0


def test_the_cached_readers_read_the_held_back_cell(tmp_path):
    """Each reader file as a later benchmark PR will list it: an entry
    beside the held-back cell's own, nothing else added."""
    for tree in ("benchmarks", "persia_tpu", "native"):
        os.symlink(os.path.join(ROOT, tree), tmp_path / tree)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    (held,) = [w["name"] for w in HELD_BACK["workloads"]]
    doc["workloads"] += HELD_BACK["workloads"]
    doc["per_layer"] += HELD_BACK["per_layer"]
    for m in doc["per_layer"]:
        if m["name"] in HELD_BACK["also_listed_by"]:
            m["workloads"] += [held]
    doc["per_layer"] += [
        {"name": name, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": layer,
         "moves": "samples_per_s", "workloads": [held]}
        for name, layer in CACHED_READERS.items()]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    assert manifest.Manifest(str(tmp_path)).validate()
    for name in CACHED_READERS:
        assert os.path.exists(
            os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py"))
    tracing.default_collector().clear()
    line = rehearse(held, 1, root=str(tmp_path))
    assert set(CACHED_READERS) <= set(line["read"])
    assert "place_batch_ms" not in line["read"]
    per_step = {}
    for s in tracing.default_collector().recent():
        if s.profiled:
            per_step[s.name] = per_step.get(s.name, 0) + 1
    steps = per_step["trainer/train_step"]
    for name in ("cache/map", "cache/miss_import", "trainer/dispatch",
                 "cache/finish"):
        assert per_step[name] == steps, (name, per_step)
