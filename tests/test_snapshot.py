"""Coordinated job-snapshot protocol units (persia_tpu/snapshot.py):
manifest completeness + torn refusal, newest-complete fallback,
retention GC, resolve/restore round trips, and the cursor doc. The
full-fleet SIGKILL matrix is tests/test_chaos_job.py; these are the
fast in-process invariants it builds on."""

import json
import os

import numpy as np
import pytest

from persia_tpu import snapshot as snap_mod
from persia_tpu.config import EmbeddingSchema, SlotConfig
from persia_tpu.data.batch import IDTypeFeature
from persia_tpu.ps.store import EmbeddingHolder
from persia_tpu.snapshot import (
    SnapshotError,
    gc_snapshots,
    latest_snapshot,
    list_snapshots,
    load_manifest,
    resolve_snapshot,
    restore_job,
    snapshot_job,
)
from persia_tpu.worker.worker import EmbeddingWorker

DIM = 4


def _counting_worker(n_ps=2):
    """Zero-init + sgd lr=1 + unit grads -> row value == -count: the
    same arm the chaos cells gate on, so equality checks are exact."""
    schema = EmbeddingSchema(slots_config={
        "clicks": SlotConfig(name="clicks", dim=DIM),
    })
    clients = [EmbeddingHolder(capacity=10_000, num_internal_shards=2)
               for _ in range(n_ps)]
    w = EmbeddingWorker(schema, clients)
    w.configure_parameter_servers(
        "bounded_uniform", {"lower": 0.0, "upper": 0.0}, 1.0, 1e9)
    w.register_optimizer({"type": "sgd", "lr": 1.0, "wd": 0.0})
    return w


def _train(worker, signs):
    ref, out = worker.lookup_direct_training(
        [IDTypeFeature("clicks", [np.asarray(signs, np.uint64)])])
    worker.update_gradients(ref, {
        k: np.ones_like(v.embeddings) for k, v in out.items()})


def _counts(worker, signs):
    """Applied per-sign counts read back through a serving lookup."""
    rows = worker.lookup_signs(np.asarray(signs, np.uint64), DIM)
    return -rows.sum(axis=1) / DIM


def test_snapshot_complete_round_trip(tmp_path):
    w = _counting_worker()
    signs = [3, 5, 5, 9]
    _train(w, signs)
    cursor = {"seed": 7, "consumed": 1}
    snap = snapshot_job(str(tmp_path), w, cursor=cursor, step=1)
    assert os.path.basename(snap) == "snap_000000"  # zero-based seq

    manifest = load_manifest(snap)
    assert manifest["step"] == 1
    assert manifest["cursor"] == cursor
    assert manifest["num_shards"] == 2
    # every payload is checksummed; the manifest itself is not listed
    assert "manifest.json" not in manifest["files"]
    assert "cursor.json" in manifest["files"]
    assert snap_mod.load_cursor(snap) == cursor

    # train PAST the snapshot, then roll back: post-snapshot updates
    # must be wiped (clear=True), restoring the exact snapshot counts
    _train(w, [3, 3, 11])
    got = restore_job(snap, w)
    assert got["seq"] == manifest["seq"]
    np.testing.assert_allclose(_counts(w, [3, 5, 9, 11]),
                               [1.0, 2.0, 1.0, 0.0], atol=1e-6)


def test_torn_snapshot_refused_and_fallback(tmp_path):
    w = _counting_worker()
    _train(w, [1, 2])
    good = snapshot_job(str(tmp_path), w, cursor={"seed": 1, "consumed": 1},
                        step=1)
    _train(w, [2, 4])
    torn = snapshot_job(str(tmp_path), w, cursor={"seed": 1, "consumed": 2},
                        step=2)

    # tear the newer snapshot: truncate one checksummed payload
    victim = sorted(load_manifest(torn)["files"])[0]
    with open(os.path.join(torn, victim), "wb") as f:
        f.write(b"torn")
    with pytest.raises(SnapshotError, match="torn write|checksum"):
        load_manifest(torn)

    # a manifest-less directory (killed pre-manifest) is refused too
    os.makedirs(os.path.join(str(tmp_path), "snap_000099"))
    found = latest_snapshot(str(tmp_path))
    assert found is not None
    path, manifest = found
    assert path == good  # fell back past BOTH torn candidates
    assert manifest["step"] == 1


def test_latest_snapshot_cold_start_and_missing_dir(tmp_path):
    assert latest_snapshot(str(tmp_path / "nope")) is None
    assert latest_snapshot(str(tmp_path)) is None
    with pytest.raises(SnapshotError, match="no complete snapshot"):
        resolve_snapshot(str(tmp_path))


def test_manifest_missing_file_refused(tmp_path):
    w = _counting_worker()
    _train(w, [1])
    snap = snapshot_job(str(tmp_path), w, cursor={"seed": 0, "consumed": 0})
    victim = sorted(load_manifest(snap)["files"])[0]
    os.remove(os.path.join(snap, victim))
    with pytest.raises(SnapshotError, match="missing"):
        load_manifest(snap)


def test_gc_retention_keeps_newest_completes(tmp_path):
    w = _counting_worker()
    for k in range(5):
        _train(w, [k + 1])
        snapshot_job(str(tmp_path), w, cursor={"seed": 0, "consumed": k},
                     step=k, keep=2)
    names = [os.path.basename(p) for p in list_snapshots(str(tmp_path))]
    assert names == ["snap_000003", "snap_000004"]
    # sequence numbers keep advancing past GC'd snapshots
    nxt = snapshot_job(str(tmp_path), w, cursor={"seed": 0, "consumed": 5},
                       keep=2)
    assert os.path.basename(nxt) == "snap_000005"


def test_gc_spares_torn_newer_than_newest_complete(tmp_path):
    """A torn directory NEWER than the newest complete snapshot may be
    a snapshot in progress — GC must leave it alone; torn debris OLDER
    than the newest complete is removed."""
    w = _counting_worker()
    _train(w, [1])
    os.makedirs(os.path.join(str(tmp_path), "snap_000000"))  # old debris
    with open(os.path.join(str(tmp_path), "snap_000000", "junk"), "wb") as f:
        f.write(b"x")
    snapshot_job(str(tmp_path), w, cursor={"seed": 0, "consumed": 0},
                 keep=3)  # becomes snap_000001 and GCs the debris
    names = [os.path.basename(p) for p in list_snapshots(str(tmp_path))]
    assert names == ["snap_000001"]
    in_progress = os.path.join(str(tmp_path), "snap_000002")
    os.makedirs(in_progress)
    removed = gc_snapshots(str(tmp_path), keep=3)
    assert removed == []
    assert os.path.isdir(in_progress)  # spared: newer than the complete


def test_resolve_snapshot_parent_vs_direct(tmp_path):
    w = _counting_worker()
    _train(w, [1])
    first = snapshot_job(str(tmp_path), w, cursor={"seed": 0, "consumed": 1})
    _train(w, [2])
    second = snapshot_job(str(tmp_path), w, cursor={"seed": 0, "consumed": 2})
    # parent dir -> newest complete; direct path -> that snapshot
    assert resolve_snapshot(str(tmp_path))[0] == second
    assert resolve_snapshot(first)[1]["cursor"]["consumed"] == 1


def test_manifest_tamper_detected(tmp_path):
    w = _counting_worker()
    _train(w, [1])
    snap = snapshot_job(str(tmp_path), w, cursor={"seed": 0, "consumed": 0})
    victim = sorted(load_manifest(snap)["files"])[0]
    path = os.path.join(snap, victim)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:  # same size, different bytes
        f.seek(max(0, size - 1))
        last = f.read(1)
        f.seek(max(0, size - 1))
        f.write(bytes([last[0] ^ 0xFF]))
    with pytest.raises(SnapshotError, match="checksum"):
        load_manifest(snap)


def test_restore_onto_wider_fleet(tmp_path):
    """Cross-topology restore: a 2-shard snapshot loads consistently
    onto a 3-replica fleet via the dump-time ownership filter."""
    w2 = _counting_worker(n_ps=2)
    _train(w2, [3, 5, 5, 9])
    snap = snapshot_job(str(tmp_path), w2, cursor={"seed": 0, "consumed": 1})
    w3 = _counting_worker(n_ps=3)
    restore_job(snap, w3)
    np.testing.assert_allclose(_counts(w3, [3, 5, 9]),
                               [1.0, 2.0, 1.0], atol=1e-6)


def test_snapshot_manifest_is_fsynced_atomic(tmp_path, monkeypatch):
    """The completeness stamp must go through the durable write path:
    manifest.json lands via write_bytes_atomic (tmp + fsync + rename +
    parent-dir fsync), never a plain open/write."""
    import persia_tpu.storage as storage

    synced = []
    real = os.fsync
    monkeypatch.setattr(storage.os, "fsync",
                        lambda fd: (synced.append(fd), real(fd)))
    w = _counting_worker()
    _train(w, [1])
    snap = snapshot_job(str(tmp_path), w, cursor={"seed": 0, "consumed": 0})
    assert len(synced) >= 2  # manifest tmp file + snapshot dir
    assert not os.path.exists(os.path.join(snap, "manifest.json.tmp"))
    load_manifest(snap)  # and the result verifies
