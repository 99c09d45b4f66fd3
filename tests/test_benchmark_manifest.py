"""Tier-1 guard of the chip benchmark's manifest: ``BENCHMARK.json``
passes the harness's own ``validate()``, and every file it names, by an
entry or through a cell or a mix, is in the tree. A PR that adds an entry
without its file, or drops a file an entry needs, fails here on the CPU
before the driver's check would refuse it on the chip."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks", "chip")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import manifest  # noqa: E402
import traffic  # noqa: E402

MAN = manifest.Manifest(ROOT)


def test_the_manifest_validates():
    assert MAN.validate()
    assert MAN.doc["paths"] == ["benchmarks/chip"]
    assert len(json.dumps(MAN.doc)) < 64 * 1024
    # the driver's limit on every line of prose, which validate() holds
    # only the cells to
    for entry in MAN.doc["configs"] + MAN.doc["workloads"]:
        for key in ("why", "source"):
            assert 0 < len(entry.get(key, "x")) <= 200, (entry["name"], key)


@pytest.mark.parametrize("config", [c["name"] for c in MAN.doc["configs"]])
def test_a_configuration_s_file_is_there_and_names_itself(config):
    entry = next(c for c in MAN.doc["configs"] if c["name"] == config)
    path = os.path.join(ROOT, entry["file"])
    assert os.path.exists(path), path
    with open(path) as f:
        doc = json.load(f)
    assert doc["name"] == config and doc["source"] == entry["source"]
    assert set(entry["reduced"]) == set(doc["reduced"])
    assert any(w["config"] == config for w in MAN.doc["workloads"])


@pytest.mark.parametrize("cell", [w["name"] for w in MAN.doc["workloads"]])
def test_a_cell_s_files_are_there(cell):
    entry, doc, config, mix_file = MAN.cell(cell)
    assert doc["name"] == cell and doc["chips"] == entry["chips"]
    assert doc["config"] == entry["config"] == config["name"]
    assert doc["mix"] == entry["traffic"]
    assert os.path.exists(MAN.path("placements", f"{doc['placement']}.py"))
    mix = traffic.load_mix(mix_file)     # raises where the generator is not
    if mix["kind"] != traffic.OWN_KIND:
        assert os.path.exists(mix["generator"])
    for key in ("sizes", "rehearsal", "limits", "rehearsal_limits",
                "predictions"):
        assert key in doc, key
    reported = {m["name"] for m in MAN.metrics_of(cell, "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    assert MAN.metrics_of(cell, "per_layer")


@pytest.mark.parametrize("metric",
                         [m["name"] for m in MAN.doc["per_layer"]])
def test_a_per_layer_metric_has_its_reader(metric):
    path = MAN.path("layer_metrics", f"{metric}.py")
    assert os.path.exists(path), path
    with open(path) as f:
        assert "def read(" in f.read()
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == metric)
    cells = {w["name"] for w in MAN.doc["workloads"]}
    assert set(entry.get("workloads", [])) <= cells
