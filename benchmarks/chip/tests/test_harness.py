"""The harness end to end on the CPU at each cell's rehearsal sizes: the
result line's shape, the manifest's rules, that a new cell needs new files
only, and that ``correct`` comes out false when the timed path is broken
or the control stands in the program's place.

The cached cell is no cell of the benchmark (PERF.md section 7), but its
files are kept: ``held_back.json`` holds its manifest entries, and the
tests rehearse it through a copy of the manifest with those put back."""

import io
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import calibrate
import check
import describe_trace
import manifest
import run as harness

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = manifest.repo_root(BENCH_DIR)
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


with open(os.path.join(BENCH_DIR, "tests", "held_back.json")) as f:
    HELD_BACK = json.load(f)


def cells(held_back=True):
    return [w["name"] for w in manifest.Manifest(ROOT).doc["workloads"]
            + (HELD_BACK["workloads"] if held_back else [])]


@pytest.fixture(scope="session")
def root_of(tmp_path_factory):
    """cell -> the root its manifest is read from: the repo's, or for a
    cell held back a directory with the repo's trees linked in and a
    manifest that has the held-back entries again."""
    root = tmp_path_factory.mktemp("held_back")
    for tree in ("benchmarks", "persia_tpu", "native"):
        os.symlink(os.path.join(ROOT, tree), root / tree)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    held = [w["name"] for w in HELD_BACK["workloads"]]
    doc["workloads"] += HELD_BACK["workloads"]
    doc["per_layer"] += HELD_BACK["per_layer"]
    for m in doc["per_layer"]:
        if m["name"] in HELD_BACK["also_listed_by"]:
            m["workloads"] += held
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert manifest.Manifest(str(root)).validate()
    return lambda cell: str(root) if cell in held else ROOT


def rehearse(cell, trace, root=ROOT, wrap_runner=None, seed=2**31 + 77):
    out = io.StringIO()
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "1.5", "--trace", str(trace),
                       "--rehearse"], root=root, out=out,
                      wrap_runner=wrap_runner)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_manifest_keeps_the_rules():
    man = manifest.Manifest(ROOT)
    assert man.validate()
    assert set(man.doc) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}
    for m in man.doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in man.doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert len(m["unit"]) <= 16
    for w in man.doc["workloads"]:
        _, cell, config, mix = man.cell(w["name"])
        assert cell["name"] == w["name"] and cell["chips"] == w["chips"]
        assert config["name"] == w["config"]


@pytest.mark.parametrize("broken,msg", [
    (lambda d: d["end_to_end"][0].update(unit="samples per second"), "unit"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda d: d["workloads"][0].update(name="a cell"), "name"),
    (lambda d: d["per_layer"].append(dict(d["per_layer"][0],
                                          name="no_reader")), "reader"),
])
def test_validate_refuses(broken, msg):
    man = manifest.Manifest(ROOT)
    broken(man.doc)
    with pytest.raises((ValueError, FileNotFoundError), match=msg):
        man.validate()


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_s_line(cell, trace, root_of):
    man = manifest.Manifest(root_of(cell))
    line = rehearse(cell, trace, root=root_of(cell))
    assert CONTRACT_KEYS <= set(line)
    assert set(line) - CONTRACT_KEYS <= {"compared", "read", "rehearsal"}
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # a CPU run prints no number under a metric's name
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in man.metrics_of(cell, group)}
    assert set(line["read"]) <= names
    if not trace:
        assert set(line["read"]) == names
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]


def test_without_a_tpu_there_is_no_result(capsys):
    out = io.StringIO()
    rc = harness.main(["--workload", cells(False)[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"], out=out)
    assert rc == 2 and out.getvalue() == ""


def _unchanged(runner):
    """A step that returns its state unchanged."""
    import jax.numpy as jnp

    if hasattr(runner, "_step"):     # device: the jitted step itself
        runner._step = lambda p, o, *_: (p, o, jnp.float32(0.69))
    else:           # cached, or a later placement: nothing reaches it
        runner.step = lambda feed: jnp.float32(0.69)


def _half_batch(runner):
    """Half of the batch left out, the mean taken over the rest: every
    array of the batch cut along its first axis, whatever its name."""
    whole = runner.convert

    def convert(b):
        return whole({k: v[:len(v) // 2] if hasattr(v, "shape") else v
                      for k, v in b.items()})

    runner.convert = convert


def one_cell_of_each_placement():
    seen = {}
    for name in cells():
        with open(os.path.join(BENCH_DIR, "cells", f"{name}.json")) as f:
            seen.setdefault(json.load(f)["placement"], name)
    return sorted(seen.values())


@pytest.mark.parametrize("cell", one_cell_of_each_placement())
@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_a_broken_timed_path_is_not_correct(cell, fault, root_of):
    line = rehearse(cell, 0, root=root_of(cell), wrap_runner=fault)
    assert line["correct"] is False
    over = [n for n, c in line["compared"].items()
            if not c["value"] <= c["limit"]]
    assert over, line["compared"]


@pytest.mark.parametrize("cell", cells())
def test_calibrate_holds_control_and_faults_to_the_limits(cell, root_of):
    """``tools/calibrate.py`` at rehearsal size, three seeds: the program
    comes out correct, and the reference in fp8, with half of the batch
    left out, or with its state unchanged, put in the program's place,
    not correct, each judged by the cell's own (rehearsal) limits."""
    out = io.StringIO()
    rc = calibrate.main(["--workload", cell, "--first-seed", str(2**31 + 5),
                         "--seeds", "3", "--control-seeds", "3",
                         "--rehearse"], root=root_of(cell), out=out)
    lines = [json.loads(x) for x in out.getvalue().splitlines()]
    assert rc == 0, [(x["side"], x["seed"], x["numbers"]) for x in lines
                     if x["correct"] != (x["side"] == "program")]
    assert len(lines) == 12
    for x in lines:
        assert set(x["numbers"]) == set(check.NUMBERS)
        assert x["correct"] == (x["side"] == "program")
        assert bool(x["over"]) == (not x["correct"])


def test_describe_trace_writes_what_a_builder_looks_at(tmp_path):
    out = tmp_path / "trace.txt"
    rc = describe_trace.main(
        ["--out", str(out), "--workload", cells(False)[0], "--seed", "5",
         "--seconds", "1.5", "--rehearse"])
    assert rc == 0 and "PLANE" in out.read_text()
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace"))


def test_spans_follow_the_measured_step():
    """A span is the fewest steps that take a quarter second at the
    median gap; one stall among hundreds of steps moves the longest gap
    and not the 95th percentile."""
    stamps = [0.03 * (i + 1) for i in range(400)]
    assert harness.span_steps(harness.single_gaps(stamps, 0.0)) == 9
    steady = harness.step_ms_p95(stamps, 0.0)
    assert steady == pytest.approx(30.0)
    stalled = [s + (0.15 if i >= 200 else 0.0)
               for i, s in enumerate(stamps)]
    assert harness.step_ms_p95(stalled, 0.0) == pytest.approx(30.0)
    assert max(harness.single_gaps(stalled, 0.0)) == pytest.approx(0.18)
    twice_as_fast = [s / 2 for s in stamps]
    assert harness.span_steps(harness.single_gaps(twice_as_fast, 0.0)) == 17
    assert harness.single_gaps(stamps, 0.0, until=0.1) == pytest.approx(
        [0.03] * 3)


def test_a_new_cell_is_new_files_only(tmp_path):
    """A later PR's cell, configuration, mix and per-layer metric: files
    added beside the old ones and entries added to the manifest."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH_DIR, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "persia_tpu"), root / "persia_tpu")
    bench = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    doc = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    old = doc["workloads"][0]["name"]
    cfg = json.loads((bench / "configs" / "dlrm-kaggle.json").read_text())
    cfg["name"] = "dlrm-extra"
    (bench / "configs" / "dlrm-extra.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "mixes" / "zipf.json").read_text())
    mix.update(name="hot", alpha=1.5)
    (bench / "mixes" / "hot.json").write_text(json.dumps(mix))
    cell = json.loads((bench / "cells" / f"{old}.json").read_text())
    cell.update(name="dlrm-extra.device-hot", config="dlrm-extra",
                mix="hot")
    (bench / "cells" / "dlrm-extra.device-hot.json").write_text(
        json.dumps(cell))
    (bench / "layer_metrics" / "late_batches.py").write_text(
        "def read(r):\n    return r.late\n")
    doc["configs"].append({"name": "dlrm-extra", "source": "test",
                           "file": "benchmarks/chip/configs/dlrm-extra.json",
                           "reduced": [], "why": "test"})
    assert "cached" not in old
    doc["workloads"].append({"name": "dlrm-extra.device-hot",
                             "config": "dlrm-extra", "traffic": "hot",
                             "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "late_batches", "unit": "count",
                             "better": "lower", "source": "program_counter",
                             "layer": "entry / input",
                             "moves": "samples_per_s",
                             "workloads": ["dlrm-extra.device-hot"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    line = rehearse("dlrm-extra.device-hot", 1, root=str(root))
    assert line["correct"] is True and "late_batches" in line["read"]
    assert all(p.read_bytes() == b for p, b in before.items())


DLRM_KEYS = {"embedding_dim", "num_dense", "bottom_mlp", "top_mlp",
             "table_cardinalities", "max_ind_range", "dense_optimizer",
             "compute_dtype"}


def test_a_cell_of_another_family_is_new_files_only(tmp_path):
    """A later PR's cell of another tower family (``tests/fixtures/
    other_family``: a generator kind, a configuration with none of DLRM's
    keys, a placement with its own reference, two per-layer metrics)
    laid as new files over a copy that has ``tests/`` too: the copy's own
    tests that parametrise over cells pass for it, and no file that was
    there changed."""
    root = tmp_path / "repo"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for tree in ("persia_tpu", "native"):
        os.symlink(os.path.join(ROOT, tree), root / tree)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    fixture = bench / "tests" / "fixtures" / "other_family"
    added = []
    for src in fixture.rglob("*"):
        rel = src.relative_to(fixture)
        if src.is_file() and len(rel.parts) > 1:
            assert not (bench / rel).exists(), rel
            (bench / rel).parent.mkdir(exist_ok=True)
            shutil.copy(src, bench / rel)
            added.append(str(rel))
    assert {os.path.dirname(a) for a in added} == {
        "generators", "mixes", "configs", "placements", "cells",
        "layer_metrics"}
    entries = json.loads((fixture / "manifest_entries.json").read_text())
    (cell,) = [w["name"] for w in entries["workloads"]]
    for c in entries["configs"]:
        assert not DLRM_KEYS & set(json.loads((root / c["file"]).read_text()))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for group, new in entries.items():
        doc[group] += new
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    done = subprocess.run(
        [sys.executable, "-m", "pytest", str(bench / "tests"), "-q",
         "-p", "no:cacheprovider", "-k", f"{cell} or manifest_keeps"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    tail = done.stdout[-3000:] + done.stderr[-1000:]
    assert done.returncode == 0, tail
    # traced and untraced rehearsal, two faults, calibrate, the manifest
    assert re.search(r"\b6 passed", done.stdout), tail
    assert all(p.read_bytes() == b for p, b in before.items())
