"""The whole-job kill matrix around the coordinated-snapshot + resume
protocol (persia_tpu/snapshot.py).

Trainer cells SIGKILL the supervised trainer driver
(persia_tpu.service.trainer_service) at a named point; the ServiceCtx
supervisor respawns it, the replacement rolls the WHOLE job back to the
newest complete snapshot (PS stores wiped to the snapshot's consistent
cut) and replays the deterministic batch stream from the snapshotted
cursor — so the per-sign counting identity must come out EXACT, with
zero ambiguity. The worker cell kills the embedding-worker tier under a
live driving loop: updates acked to the dead worker but not yet
confirmed settled on the PS are the DECLARED ambiguity the loss bound
is held to. ``torn_manifest`` and ``during_reshard`` exercise the
snapshot machinery itself; ``convergence`` holds a resumed zoo-DLRM run
through TrainCtx(resume_from=) to an unbroken baseline. The PS-tier
kill is tests/test_faults.py's supervised-restart test.

One file of its own: ``--dist loadfile`` keeps the matrix on one worker.
"""

import itertools
import json
import os
import threading
import time

import numpy as np
import pytest

from persia_tpu import snapshot as snap_mod
from persia_tpu.config import EmbeddingSchema, uniform_slots
from persia_tpu.data.batch import IDTypeFeature
from persia_tpu.service.helper import ServiceCtx
from persia_tpu.service.trainer_service import batch_draws, sign_pool
from tests.fleet_support import (
    applied_counts,
    arm_counting,
    assert_counting_identity,
    expected_counts,
    scenario_stack,
    time_limit,
    unit_update,
    validate_postmortem,
    wait_until,
)

DIM = 8
N_FEATS = 2
BS = 64

CELLS = (
    ("trainer", "mid_step"),
    ("trainer", "mid_snapshot"),
    ("trainer", "between_snapshots"),
    ("trainer", "torn_manifest"),
    ("worker", "mid_step"),
    ("snapshot", "during_reshard"),
    ("trainer", "convergence"),
)


def _schema():
    return EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{i}" for i in range(N_FEATS)], dim=DIM))


def _feats(draws):
    return [IDTypeFeature(f"slot_{i}", [d]) for i, d in enumerate(draws)]


def _trainer_killed(kind, tmp_path):
    """The driver finishes (exit 0) through the kill; at least one
    recovery with a valid postmortem bundle; the replacement actually
    RESUMED from a snapshot (mid_snapshot must have fallen back past
    the torn one); the counting identity is exact; retention kept at
    most PERSIA_SNAPSHOT_KEEP complete snapshots."""
    from persia_tpu import knobs

    seed, pool_size, steps, interval = 3, 2048, 12, 4
    # mid_step / between_snapshots kill BETWEEN cadence boundaries (one
    # complete snapshot behind them); mid_snapshot kills INSIDE the
    # second snapshot so a complete fallback exists behind the torn one
    die_step = 2 * interval if kind == "mid_snapshot" else interval + 2
    snap_dir = str(tmp_path / "snapshots")
    result_file = str(tmp_path / "result.json")
    trainer_args = [
        "--num-workers", "1", "--steps", str(steps),
        "--batch-size", str(BS), "--n-feats", str(N_FEATS),
        "--seed", str(seed), "--pool-size", str(pool_size),
        "--snapshot-interval", str(interval),
        "--die-at", kind, "--die-step", str(die_step),
        "--result-file", result_file,
        # slow the loop so flight-recorder polls land before the kill
        "--step-delay", "0.15"]
    with ServiceCtx(_schema(), n_workers=1, n_ps=2,
                    supervise_trainer=True, trainer_args=trainer_args,
                    snapshot_dir=snap_dir,
                    postmortem_dir=str(tmp_path / "postmortems"),
                    flight_interval=0.3,
                    env={"PERSIA_TRACING": "1"}) as svc:
        rc = svc.wait_trainer_done(timeout=120.0)
        assert rc == 0, (f"driver never finished (rc={rc}, recoveries="
                         f"{svc.trainer_recoveries})")
        events = list(svc.trainer_recoveries)
        assert events, "the kill never fired — zero trainer recoveries"
        validate_postmortem(events[0].get("postmortem"))
        with open(result_file) as f:
            result = json.load(f)
        assert result["steps"] == steps
        assert result.get("resumed_from"), \
            "replacement driver did not resume from a snapshot"
        if kind == "mid_snapshot":
            # the torn snap_000001 must be refused with fallback to the
            # complete one behind it
            assert result["resumed_from"] == "snap_000000"
        pool = sign_pool(pool_size)
        assert_counting_identity(
            f"trainer:{kind}", pool,
            expected_counts(pool, seed, steps, BS, N_FEATS),
            applied_counts(svc.remote_worker(), pool, DIM))
        complete = 0
        for p in snap_mod.list_snapshots(snap_dir):
            try:
                snap_mod.load_manifest(p)
                complete += 1
            except snap_mod.SnapshotError:
                pass
        assert 1 <= complete <= int(knobs.get("PERSIA_SNAPSHOT_KEEP"))


def _torn_manifest(tmp_path):
    """Through the public snapshot API against a live (unsupervised)
    fleet: corrupt the newest snapshot's payload; verification refuses
    it, latest_snapshot falls back to the previous complete one, and
    restoring that fallback rolls the PS stores back to its exact cut
    (post-snapshot updates wiped)."""
    seed = 11
    snap_dir = str(tmp_path / "snapshots")
    pool = sign_pool(2048)
    with ServiceCtx(_schema(), n_workers=1, n_ps=2) as svc:
        w = svc.remote_worker()
        arm_counting(w)

        def train(k0, k1):
            for k in range(k0, k1):
                unit_update(w, _feats(
                    batch_draws(pool, seed, k, BS, N_FEATS)))

        train(0, 4)
        snap1 = snap_mod.snapshot_job(
            snap_dir, w, cursor={"seed": seed, "consumed": 4}, step=4)
        train(4, 8)
        snap2 = snap_mod.snapshot_job(
            snap_dir, w, cursor={"seed": seed, "consumed": 8}, step=8)
        # tear the newest snapshot: truncate a manifest-listed payload
        victim = sorted(snap_mod.load_manifest(snap2)["files"])[0]
        with open(os.path.join(snap2, victim), "wb") as f:
            f.write(b"torn")
        with pytest.raises(snap_mod.SnapshotError):
            snap_mod.load_manifest(snap2)
        # a manifest-less dir newer than everything must also be skipped
        os.makedirs(os.path.join(snap_dir, "snap_000099"))
        found = snap_mod.latest_snapshot(snap_dir)
        assert found is not None
        assert os.path.basename(found[0]) == os.path.basename(snap1)
        snap_mod.restore_job(found[0], w)
        assert_counting_identity(
            "trainer:torn_manifest", pool,
            expected_counts(pool, seed, 4, BS, N_FEATS),
            applied_counts(w, pool, DIM))


def _worker_killed(tmp_path):
    """Workers are stateless past their in-flight update queue, so the
    job does NOT roll back — the supervisor respawns the replica under
    the same coordinator index and the loop re-resolves. The ledger
    splits acked updates into CONFIRMED (a later worker.staleness == 0
    poll proved them applied on the PS) and pending:

    - confirmed-at-kill updates are NEVER lost (elementwise);
    - total loss is bounded by the DECLARED ambiguity (acked-but-
      unconfirmed at kill + failed cycles) — never silent;
    - over-application is bounded by the failed cycles (client retries
      against a fresh dedup cache are at-least-once);
    - the killed worker leaves a valid postmortem bundle."""
    from persia_tpu import tracing
    from persia_tpu.service.worker_service import RemoteEmbeddingWorker

    pool = sign_pool(4096)
    tracing.enable_tracing(True)
    try:
        with ServiceCtx(_schema(), n_workers=1, n_ps=2,
                        supervise_workers=True,
                        postmortem_dir=str(tmp_path / "postmortems"),
                        flight_interval=0.3,
                        env={"PERSIA_TRACING": "1"}) as svc:

            def mk_worker():
                w = RemoteEmbeddingWorker(list(svc.worker_addrs))
                arm_counting(w)
                return w

            worker_box = [mk_worker()]
            a_lock = threading.Lock()
            stop = threading.Event()
            expected = np.zeros(len(pool), np.int64)   # every acked cycle
            confirmed = np.zeros(len(pool), np.int64)  # settled on the PS
            acked = [0]
            settled = [0]
            pending = []   # (elems, idx) acked, settlement unconfirmed
            failures = []  # elems per failed cycle

            def train():
                rng = np.random.default_rng(5)
                while not stop.is_set():
                    draws = [rng.choice(pool, size=BS)
                             for _ in range(N_FEATS)]
                    idx = np.searchsorted(pool, np.concatenate(draws))
                    # the WHOLE cycle (RPC + ledger) runs under the
                    # lock; the killer takes the same lock, so a kill
                    # never lands between an ack and its bookkeeping
                    with a_lock:
                        if stop.is_set():
                            return
                        w = worker_box[0]
                        try:
                            unit_update(w, _feats(draws))
                        except Exception:  # noqa: BLE001
                            failures.append(N_FEATS * BS)
                            worker_box[0] = None
                        else:
                            acked[0] += N_FEATS * BS
                            np.add.at(expected, idx, 1)
                            pending.append((N_FEATS * BS, idx))
                            try:
                                if w.staleness == 0:
                                    for e, pidx in pending:
                                        settled[0] += e
                                        np.add.at(confirmed, pidx, 1)
                                    pending.clear()
                            except Exception:  # noqa: BLE001
                                pass  # unconfirmed cycles stay pending
                    if worker_box[0] is None:
                        time.sleep(0.25)
                        try:
                            worker_box[0] = mk_worker()
                        except Exception:  # noqa: BLE001
                            worker_box[0] = None
                    time.sleep(0.01)

            t = threading.Thread(target=train)
            t.start()
            try:
                # a flight snapshot that already holds traced worker
                # spans must exist before the kill
                wait_until(
                    lambda: (svc.flight_recorder.last("worker0") or {})
                    .get("spans") and acked[0] > 0,
                    20, "flight recorder never saw the worker's spans")
                with a_lock:
                    acked_k = acked[0]
                    settled_k = settled[0]
                    confirmed_k = confirmed.copy()
                    svc.worker_proc(0).kill()
                ev = svc.wait_worker_recoveries(1, timeout=90)[0]
                assert "failed" not in ev, f"worker recovery failed: {ev}"
                # train past the recovery, on the replacement
                base = acked[0]
                wait_until(lambda: acked[0] >= base + 4 * N_FEATS * BS,
                           30, "no acked cycle after the worker respawn")
            finally:
                stop.set()
                t.join(timeout=60)
            # everything acked to the REPLACEMENT worker must drain to
            # the PS before the ledger is read
            w = worker_box[0] or mk_worker()

            def drained():
                try:
                    return w.staleness == 0
                except Exception:  # noqa: BLE001
                    return False

            wait_until(drained, 30, "replacement worker never drained",
                       interval=0.1)
            got = applied_counts(w, pool, DIM)
            fail_elems = int(sum(failures))
            declared = (acked_k - settled_k) + fail_elems
            short = np.nonzero(confirmed_k - got > 1e-3)[0]
            assert not len(short), (
                f"{len(short)} signs lost updates that were CONFIRMED "
                f"settled before the kill")
            lost = float(expected.sum()) - float(got.sum())
            assert lost <= declared + 1e-3, (
                f"lost {lost:.1f} updates > declared ambiguity "
                f"{declared} (acked@kill={acked_k}, "
                f"settled@kill={settled_k}, failed={fail_elems})")
            assert -lost <= fail_elems + 1e-3, (
                f"over-applied {-lost:.1f} beyond the {fail_elems} "
                f"failed-cycle elements")
            assert len(failures) <= 60, (
                f"{len(failures)} cycles failed — recovery is not "
                f"transparent")
            validate_postmortem(ev.get("postmortem"),
                                health_key="forward_buffer_depth")
    finally:
        tracing.enable_tracing(False)


def _snapshot_during_reshard(tmp_path):
    """A snapshot taken WHILE a live reshard migrates rows: the barrier
    + dump-time routing stamp must make the restore consistent even
    onto the post-reshard topology. The controller's phase hook takes a
    job snapshot during the copy phase (driving loop quiesced, so the
    expected cut is exact); after the migration completes and more
    training lands, restoring that snapshot must roll the 3-replica
    fleet back to the exact mid-reshard cut."""
    from persia_tpu.reshard import ReshardController
    from persia_tpu.routing import RoutingTable
    from persia_tpu.service.ps_service import PsClient
    from persia_tpu.worker.worker import EmbeddingWorker

    snap_dir = str(tmp_path / "snapshots")
    pool = sign_pool(4096)
    with ServiceCtx(_schema(), n_workers=0, n_ps=3) as svc:
        clients = [PsClient(a) for a in svc.ps_addrs]
        for c in clients:
            arm_counting(c)
        table = RoutingTable.uniform(2)
        worker = EmbeddingWorker(_schema(), clients[:2], routing=table)
        a_lock = threading.Lock()
        stop = threading.Event()
        expected = np.zeros(len(pool), np.int64)
        snap_cut = {}

        def train():
            rng = np.random.default_rng(9)
            while not stop.is_set():
                draws = [rng.choice(pool, size=BS)
                         for _ in range(N_FEATS)]
                idx = np.searchsorted(pool, np.concatenate(draws))
                with a_lock:  # full cycle under the lock: the snapshot
                    if stop.is_set():  # hook sees no half-acked cycles
                        return
                    unit_update(worker, _feats(draws))
                    np.add.at(expected, idx, 1)
                time.sleep(0.005)

        def phase_hook(st, **kw):
            if st != "copy" or snap_cut:
                return
            with a_lock:
                snap_cut["path"] = snap_mod.snapshot_job(
                    snap_dir, worker,
                    cursor={"seed": 9, "consumed": -1}, step=0)
                snap_cut["expected"] = expected.copy()
                snap_cut["epoch"] = worker.routing_epoch

        t = threading.Thread(target=train)
        t.start()
        try:
            ctrl = ReshardController(
                clients, table, workers=[worker],
                journal_dir=str(tmp_path / "journal"),
                drain_sec=0.25, replay_settle_rows=64,
                phase_hook=phase_hook)
            new_table = ctrl.reshard_to(3)
            ctrl.finalize(drain_sec=0.3)
            base = int(expected.sum())
            wait_until(
                lambda: int(expected.sum()) >= base + 4 * N_FEATS * BS,
                30, "no training landed after the reshard")
        finally:
            stop.set()
            t.join(timeout=60)
        assert "path" in snap_cut, "the copy-phase hook never fired"
        manifest = snap_mod.load_manifest(snap_cut["path"])
        assert manifest.get("routing_epoch") == snap_cut["epoch"]
        assert worker.routing_epoch == new_table.epoch
        # restore the MID-RESHARD snapshot onto the POST-reshard fleet
        snap_mod.restore_job(snap_cut["path"], worker)
        assert_counting_identity(
            "snapshot:during_reshard", pool, snap_cut["expected"],
            applied_counts(worker, pool, DIM))
        worker.close()


def _resumed_run_converges_identically(tmp_path):
    """A baseline run trains N steps straight; a crashed run trains N/2
    steps, takes a job snapshot (dense model + optimizer state, sparse
    stores, cursor) and is discarded; a THIRD stack — fresh, empty —
    resumes via TrainCtx(resume_from=) and trains the remaining batches
    from the snapshotted cursor. The per-step losses of the replayed
    suffix, the final dense parameters and the held-out AUC must match
    the baseline (deterministic CPU training: the rollback is exact, so
    divergence means the snapshot lost or corrupted state)."""
    import jax

    from persia_tpu.workloads import evaluate_auc, get_scenario

    sc = get_scenario("dlrm", smoke=True)
    bs = sc.bench_batch_size
    n_steps = 60
    half = n_steps // 2
    snap_dir = str(tmp_path / "snapshots")

    def run(start=0, stop_at=None, resume_from=None):
        ctx, worker, _holders = scenario_stack(sc, resume_from=resume_from)
        losses = []
        with ctx:
            loss = None
            for b in itertools.islice(sc.batches(n_steps * bs, bs),
                                      start, stop_at):
                loss, _ = ctx.train_step(b)
                losses.append(float(loss))
            jax.block_until_ready(loss)
            if stop_at is not None:  # the to-be-"crashed" run
                ctx.snapshot(snap_dir,
                             cursor={"seed": sc.seed, "consumed": stop_at})
                worker.close()
                return losses, None, None
            aucs = evaluate_auc(ctx, sc, num_samples=2048,
                                batch_size=min(bs, 512))
            params = jax.device_get(ctx.state.params)
        worker.close()
        return losses, aucs, params

    base_losses, base_aucs, base_params = run()
    run(stop_at=half)  # crashes here; only its snapshot survives
    found = snap_mod.latest_snapshot(snap_dir)
    assert found is not None, "mid-run snapshot missing"
    start = int((found[1].get("cursor") or {}).get("consumed", 0))
    assert start == half
    res_losses, res_aucs, res_params = run(start=start,
                                           resume_from=snap_dir)
    np.testing.assert_allclose(
        res_losses, base_losses[half:], rtol=0, atol=1e-5,
        err_msg="replayed-suffix losses diverged from the baseline — "
                "the resumed job is not the same job")
    for a, b in zip(jax.tree_util.tree_leaves(base_params),
                    jax.tree_util.tree_leaves(res_params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=0, atol=1e-5)
    for k in base_aucs:
        assert abs(base_aucs[k] - res_aucs[k]) <= 1e-6, \
            (base_aucs, res_aucs)


@pytest.mark.parametrize("actor,state", CELLS,
                         ids=[f"{a}-{s}" for a, s in CELLS])
def test_kill_during_job(actor, state, tmp_path):
    with time_limit(200, f"job kill cell {actor}:{state}"):
        if state == "torn_manifest":
            _torn_manifest(tmp_path)
        elif state == "convergence":
            _resumed_run_converges_identically(tmp_path)
        elif actor == "trainer":
            _trainer_killed(state, tmp_path)
        elif actor == "worker":
            _worker_killed(tmp_path)
        else:
            _snapshot_during_reshard(tmp_path)
