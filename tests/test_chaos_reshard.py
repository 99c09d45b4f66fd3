"""The live-resharding kill matrix: SIGKILL each protocol actor
(controller / donor PS / target PS) at each protocol state (copy,
replay, freeze, cutover, drain) under live counting traffic. Every cell
asserts that

- the migration either completes or aborts to a consistent epoch, and a
  follow-up controller (resume-from-journal for controller kills, plain
  retry after supervisor recovery for PS kills) drives it to completion;
- the counting-optimizer identity shows ZERO lost updates (the PS-kill
  cells hold it sign by sign, and bound over-application by the
  in-flight-at-kill ambiguity: at-least-once across a server restart);
- a killed PS leaves a valid flight-recorder bundle, a killed controller
  a resumable journal;
- the lease cell: a dead controller's frozen donors thaw themselves and
  traffic flows again under the old epoch before anyone resumes.

One file of its own: ``--dist loadfile`` keeps the matrix on one worker.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from persia_tpu.config import EmbeddingSchema, uniform_slots
from persia_tpu.data.batch import IDTypeFeature
from persia_tpu.reshard import MigrationJournal, ReshardController
from persia_tpu.routing import RoutingTable
from persia_tpu.service.ps_service import PsClient
from persia_tpu.worker.worker import EmbeddingWorker
from tests.fleet_support import (
    COUNTING_ARM,
    arm_counting,
    owner_filtered_applied,
    time_limit,
    unit_update,
    validate_postmortem,
    wait_until,
)

DIM = 8
N_FEATS = 2
BS = 128
STATES = ("copy", "replay", "freeze", "cutover", "drain")

CELLS = (
    [("controller", s) for s in STATES]
    + [("donor", s) for s in STATES]
    + [("target", s) for s in ("copy", "replay", "cutover")]
    + [("lease", "freeze")]
)


def _schema():
    return EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{i}" for i in range(N_FEATS)], dim=DIM))


def _controller_cell(state, tmp_path, monkeypatch, lease_cell):
    """In-process PS fleet, a REAL subprocess controller SIGKILLed
    (faults ``die``) at ``state``; then either an immediate resume from
    the journal or — the lease cell — the donors must auto-thaw first."""
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service.ps_service import PsService

    holders = [EmbeddingHolder(capacity=2_000_000) for _ in range(3)]
    services, clients = [], []
    for h in holders:
        svc = PsService(h, port=0)
        svc.server.serve_background()
        c = PsClient(svc.addr, circuit_breaker=False)
        arm_counting(c)
        services.append(svc)
        clients.append(c)
    table = RoutingTable.uniform(2)
    worker = EmbeddingWorker(_schema(), clients[:2], routing=table)
    journal = str(tmp_path / "journal")
    os.makedirs(journal)
    ships = [0]
    s_lock = threading.Lock()
    stop = threading.Event()
    errors = []

    def train(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            feats = [IDTypeFeature(f"slot_{i}", [
                rng.integers(0, 1 << 18, BS, dtype=np.uint64)])
                for i in range(N_FEATS)]
            try:
                unit_update(worker, feats)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                time.sleep(0.25)
                continue
            with s_lock:
                ships[0] += N_FEATS * BS

    def flows(n_cycles, what):
        base = ships[0]
        wait_until(lambda: ships[0] >= base + n_cycles * N_FEATS * BS,
                   20, what)

    threads = [threading.Thread(target=train, args=(s,))
               for s in range(2)]
    for t in threads:
        t.start()
    try:
        flows(4, "no live traffic before the migration")
        table_path = str(tmp_path / "table.json")
        with open(table_path, "w") as f:
            json.dump(table.to_doc(), f)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PERSIA_RESHARD_STALE_RETRY_SEC="30")
        if lease_cell:
            # short enough to see the auto-thaw promptly, with headroom
            # over the longest inter-RPC gap a donor sees while the
            # controller copies its SIBLING (every reshard RPC renews
            # the lease)
            env["PERSIA_RESHARD_FREEZE_LEASE_SEC"] = "6"
            monkeypatch.setenv("PERSIA_RESHARD_FREEZE_LEASE_SEC", "6")
        proc = subprocess.run(
            [sys.executable, "-m", "persia_tpu.reshard",
             "--journal", journal, "--ps",
             ",".join(c.addr for c in clients),
             "--table", table_path, "--to", "3", "--die-at", state],
            env=env, capture_output=True, timeout=120)
        assert proc.returncode != 0, (
            f"controller survived --die-at {state}: "
            f"{proc.stdout[-500:]!r}")
        st = MigrationJournal(journal).state()
        assert st is not None, "controller died before journaling the plan"
        assert st["phase"] not in MigrationJournal.TERMINAL, (
            f"driver reached terminal phase {st['phase']!r} instead of "
            f"dying at {state!r}: {proc.stderr[-800:]!r}")
        if lease_cell:
            # do NOT resume: the donors must heal themselves
            donors = sorted({int(mv["donor"]) for mv in st["moves"]})
            wait_until(
                lambda: all(not clients[d].reshard_status()["active"]
                            for d in donors),
                30, "frozen donors never auto-thawed after the "
                    "controller kill (lease broken)")
            flows(1, "writers did not recover after the donor auto-thaw")
        ctrl, _action = ReshardController.resume(journal, clients,
                                                 workers=[worker])
        ctrl.finalize(drain_sec=0.2)
        new_table = ctrl.table
        flows(2, "no traffic on the resumed topology")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    try:
        assert not errors, (
            f"trainer errors across the kill + resume: {errors[0]!r} "
            f"(+{len(errors) - 1} more)")
        assert new_table.epoch == table.epoch + 1
        assert new_table.num_replicas == 3
        assert worker.routing_epoch == new_table.epoch
        for i, c in enumerate(clients):
            assert not c.reshard_status()["active"], \
                f"replica {i} left with armed reshard state"
        assert MigrationJournal(journal).state()["phase"] == "finalized"
        applied = owner_filtered_applied(holders, new_table, DIM)
        assert abs(ships[0] - applied) <= 1e-3, (
            f"counting identity broken: ships={ships[0]} "
            f"applied={applied:.1f}")
    finally:
        worker.close()
        for s in services:
            s.stop()


def _ps_cell(actor, state, tmp_path):
    """Supervised PS-subprocess fleet (checkpoint + flush-per-commit
    inc packets, so every ACKED update is durable before the kill); the
    in-process controller's phase hook SIGKILLs the victim replica at
    ``state``. The supervisor restarts + restores it, the migration
    aborts to a consistent epoch (or completes, for post-role kills)
    and a fresh controller retries to completion.

    No sign may end short of its acked updates, kill or no kill.
    Over-application is bounded by the cycles in flight at the kill
    (across a restart the dedup cache is gone and a retry is
    at-least-once) plus the failed ones. Every row of the pool is
    written once before the checkpoint, so the traffic only ever
    updates rows that are durable: what becomes of a row that a lookup
    created and no update has reached yet is not this matrix's
    question (``tests/test_reshard.py::
    test_update_for_a_row_lost_before_it_lands_is_not_acked``)."""
    import yaml

    from persia_tpu import tracing
    from persia_tpu.checkpoint import dump_sharded
    from persia_tpu.service.coordinator import ROLE_PS, CoordinatorClient
    from persia_tpu.service.helper import ServiceCtx

    ckpt_dir = str(tmp_path / "ckpt")
    inc_dir = str(tmp_path / "inc")
    journal = str(tmp_path / "journal")
    gc_path = str(tmp_path / "global.yml")
    with open(gc_path, "w") as f:
        # flush-per-commit: an ACKED update is on disk before the
        # handler returns, so a SIGKILL loses only unacked work — the
        # precondition for the exact identity
        yaml.safe_dump({"parameter_server": {
            "capacity": 1_000_000, "num_hashmap_internal_shards": 4,
            "enable_incremental_update": True,
            "incremental_buffer_size": 1,
            "incremental_dir": inc_dir}}, f)
    pool = np.unique(np.random.default_rng(7).integers(
        0, 1 << 40, 8192, dtype=np.uint64))
    tracing.enable_tracing(True)
    try:
        with ServiceCtx(_schema(), n_workers=0, n_ps=3,
                        global_config_path=gc_path, supervise_ps=True,
                        ps_restore_dir=ckpt_dir, ps_inc_dir=inc_dir,
                        ps_probe_interval=0.25,
                        postmortem_dir=str(tmp_path / "postmortems"),
                        flight_interval=0.4,
                        env={"PERSIA_TRACING": "1"}) as svc:
            coord = CoordinatorClient(svc.coordinator_addr)
            clients = [PsClient(a) for a in svc.ps_addrs]
            for c in clients:
                arm_counting(c)
            # traced warmup against EVERY replica (the future target
            # included): its flight ring must hold a real
            # rpc/lookup -> ps/lookup chain for the bundle even when
            # the kill lands before it serves worker traffic
            with tracing.span("chaos_reshard/warmup"):
                for c in clients:
                    c.lookup(np.arange(16, dtype=np.uint64), DIM, False)
            table = RoutingTable.uniform(2)

            def resolver():
                fresh = [PsClient(a)
                         for a in coord.wait_members(ROLE_PS, 3, 60)]
                for c in fresh:
                    try:
                        if not c.ready_for_serving():
                            arm_counting(c)
                    except Exception:  # noqa: BLE001 — still restoring
                        pass
                return fresh

            worker = EmbeddingWorker(
                _schema(), clients[:2], routing=table,
                ps_resolver=lambda: resolver()[:worker.replica_size])
            worker._last_configure, worker._last_optimizer = COUNTING_ARM

            # every row once, so each is in the checkpoint
            unit_update(worker, [
                IDTypeFeature(f"slot_{i}", [d])
                for i, d in enumerate(np.array_split(pool, N_FEATS))])
            dump_sharded(clients[:2], ckpt_dir, routing=table)

            acked = [len(pool)]
            windows = []   # (t0, t1, pool indices) per acked cycle
            failures = []  # elems per failed cycle
            a_lock = threading.Lock()
            stop = threading.Event()
            # per-sign expected counts: the identity, sign by sign, and
            # on a miss the pointer to WHICH slot and owner dropped it
            expected = np.ones(len(pool), np.int64)

            def train(seed):
                rng = np.random.default_rng(seed)
                while not stop.is_set():
                    draws = [rng.choice(pool, size=BS)
                             for _ in range(N_FEATS)]
                    feats = [IDTypeFeature(f"slot_{i}", [d])
                             for i, d in enumerate(draws)]
                    t0 = time.monotonic()
                    try:
                        unit_update(worker, feats)
                    except Exception:  # noqa: BLE001
                        with a_lock:
                            failures.append(N_FEATS * BS)
                        time.sleep(0.25)
                        continue
                    idx = np.searchsorted(pool, np.concatenate(draws))
                    with a_lock:
                        acked[0] += len(idx)
                        windows.append((t0, time.monotonic(), idx))
                        np.add.at(expected, idx, 1)

            threads = [threading.Thread(target=train, args=(s,))
                       for s in range(2)]
            for t in threads:
                t.start()
            t_kill = []
            victim = []

            def phase_hook(st, **kw):
                if st != state or t_kill:
                    return
                idx = int(kw.get("donor", 0)) if actor == "donor" else 2
                victim.append(idx)
                t_kill.append(time.monotonic())
                svc.ps_proc(idx).kill()

            completed_first_try = False
            try:
                # a flight snapshot taken AFTER the traced warmup must
                # exist for every replica, or an early kill leaves a
                # bundle captured before any span existed
                wait_until(
                    lambda: all(
                        (svc.flight_recorder.last(f"ps{i}") or {})
                        .get("spans") for i in range(3)),
                    20, "flight recorder never saw the warmup spans")
                ctrl = ReshardController(
                    clients, table, workers=[worker],
                    journal_dir=journal, drain_sec=0.25,
                    replay_settle_rows=64, phase_hook=phase_hook)
                try:
                    new_table = ctrl.reshard_to(3)
                    ctrl.finalize(drain_sec=0.3)
                    completed_first_try = True
                except Exception:  # noqa: BLE001 — aborted by the kill
                    pass
                assert t_kill, (f"the kill never fired — the phase hook "
                                f"did not reach state {state!r}")
                ev = svc.wait_ps_recoveries(1, timeout=90)[0]
                assert "failed" not in ev, f"PS recovery failed: {ev}"
                validate_postmortem(ev.get("postmortem"))
                if not completed_first_try:
                    # the fleet must sit on a consistent OLD epoch
                    # before the retry
                    assert worker.routing_epoch == table.epoch
                    fresh = [resolver()]

                    def all_serving():
                        try:
                            if all(c.ready_for_serving()
                                   for c in fresh[0]):
                                return True
                        except Exception:  # noqa: BLE001
                            pass
                        fresh[0] = resolver()
                        return False

                    wait_until(all_serving, 60,
                               "restored fleet never became ready",
                               interval=0.25)
                    ctrl = ReshardController(
                        fresh[0], table, workers=[worker],
                        journal_dir=journal, drain_sec=0.25,
                        replay_settle_rows=64)
                    new_table = ctrl.reshard_to(3)
                    ctrl.finalize(drain_sec=0.3)
                base = acked[0]
                wait_until(lambda: acked[0] >= base + 2 * N_FEATS * BS,
                           30, "no traffic on the final topology")
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=60)
            assert len(failures) <= 24, (
                f"{len(failures)} trainer cycles failed — recovery is "
                f"not transparent")
            rows = worker.lookup_signs(pool, DIM)
            got = -rows.sum(axis=1) / DIM
            short = np.nonzero(expected - got > 1e-3)[0]
            if len(short):
                # read EVERY replica's copy of the short signs (stale
                # donor copies included): a fleet-wide total >= acked
                # means rows sit at the wrong owner, < acked means a
                # durability loss
                per_replica = [-c.get_entries(pool[short[:8]], DIM)[1]
                               .sum(axis=1) / DIM for c in resolver()]
                forensic = [
                    {"sign": int(pool[i]),
                     "slot": int(new_table.slot_of(pool[i:i + 1])[0]),
                     "old_owner": int(table.replica_of(pool[i:i + 1])[0]),
                     "new_owner": int(
                         new_table.replica_of(pool[i:i + 1])[0]),
                     "expected": int(expected[i]),
                     "got": round(float(got[i]), 1),
                     "per_replica": [round(float(pr[j]), 1)
                                     for pr in per_replica]}
                    for j, i in enumerate(short[:8])]
                raise AssertionError(
                    f"LOST UPDATES on {len(short)} signs: "
                    f"acked={acked[0]} "
                    f"applied={got.sum():.1f}; victim ps{victim[0]} "
                    f"killed at {state}; first: {forensic}")
            # cycles in flight at the kill may re-apply once after the
            # retry; failed cycles may have partially applied
            ambiguous = sum(failures) + sum(
                len(idx) for a, b, idx in windows if a <= t_kill[0] <= b)
            assert got.sum() - acked[0] <= ambiguous + 1e-3, (
                f"over-applied beyond the in-flight ambiguity budget: "
                f"acked={acked[0]} applied={got.sum():.1f} "
                f"ambiguous={ambiguous}")
            assert worker.routing_epoch == new_table.epoch
            for i, c in enumerate(resolver()):
                stat = c.reshard_status()
                assert not stat["active"], \
                    f"replica {i} left frozen/armed after the dance"
                assert (stat["routing_epoch"] or 0) <= new_table.epoch
            worker.close()
    finally:
        tracing.enable_tracing(False)


@pytest.mark.parametrize("actor,state", CELLS,
                         ids=[f"{a}-{s}" for a, s in CELLS])
def test_kill_during_reshard(actor, state, tmp_path, monkeypatch):
    if actor in ("donor", "target"):
        # 11-23 s as a rule; a trainer cycle caught on the dead replica
        # can stack several client retry ladders (up to 17 s each)
        # before it settles, and a cell read 70-100 s about once in
        # sixty kills under load
        with time_limit(240, f"reshard kill cell {actor}:{state}"):
            _ps_cell(actor, state, tmp_path)
    else:
        with time_limit(150, f"reshard kill cell {actor}:{state}"):
            _controller_cell(state, tmp_path, monkeypatch,
                             lease_cell=(actor == "lease"))
