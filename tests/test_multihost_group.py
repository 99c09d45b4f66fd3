"""Multi-process trainer group tests: round-robin stream sharding
(union of N shards == the 1-process stream, byte-wise, for every zoo
generator), per-process cursor restore + shard-mismatch refusal, the
PERSIA_MULTIHOST_CACHE negotiate-down contract, and a 2-trainer
ServiceCtx counting group whose per-sign update identity must sum
EXACTLY across the group against one shared worker/PS tier."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from persia_tpu.data.dataloader import ResumableDataset  # noqa: E402
from persia_tpu.workloads import get_scenario  # noqa: E402


# --- round-robin stream sharding -------------------------------------------


@pytest.mark.parametrize("name", ["dlrm", "seqrec", "multitask"])
def test_shard_union_is_global_stream(name):
    """Process p of N must yield exactly the global batches at stream
    positions i with i % N == p — so reassembling the shards
    round-robin reproduces the 1-process stream byte-identically (no
    batch trained twice, none skipped), for every zoo generator."""
    sc = get_scenario(name, smoke=True)
    bs, n, N = 32, 6, 3

    def factory(seed):
        return sc.batches(n * bs, bs, seed=seed)

    full = [b.to_bytes() for b in ResumableDataset(factory, seed=7)]
    assert len(full) == n

    shards = [[b.to_bytes()
               for b in ResumableDataset(factory, seed=7,
                                         process_index=p, process_count=N)]
              for p in range(N)]
    # per-shard content: exactly the i % N == p subsequence, in order
    for p in range(N):
        assert shards[p] == full[p::N]
    # union, reassembled round-robin, IS the global stream
    rebuilt = [shards[i % N][i // N] for i in range(n)]
    assert rebuilt == full


def test_shard_cursor_roundtrip_and_refusal():
    """The cursor stays in PER-PROCESS trained batches and carries
    shard coordinates only when sharded (the 1-process cursor dict is
    byte-identical to the historic format); restore reproduces the
    exact per-shard suffix; a cursor cut for another shard is refused."""
    sc = get_scenario("dlrm", smoke=True)
    bs, n, N = 32, 8, 2

    def factory(seed):
        return sc.batches(n * bs, bs, seed=seed)

    # historic single-process cursor: no shard fields
    ds1 = ResumableDataset(factory, seed=5)
    list(ds1)
    assert ds1.cursor(trained=3) == {"seed": 5, "consumed": 3}

    # sharded cursor names its shard
    ds = ResumableDataset(factory, seed=5, process_index=1, process_count=N)
    shard = [b.to_bytes() for b in ds]
    assert len(shard) == n // N
    cur = ds.cursor(trained=2)
    assert cur == {"seed": 5, "consumed": 2,
                   "process_index": 1, "process_count": N}

    # restore with explicit matching coordinates -> exact suffix
    resumed = ResumableDataset.from_cursor(factory, cur,
                                           process_index=1, process_count=N)
    assert [b.to_bytes() for b in resumed] == shard[2:]

    # restore with defaults -> the cursor's own shard (the cursor names
    # the stream cut)
    resumed2 = ResumableDataset.from_cursor(factory, cur)
    assert (resumed2.process_index, resumed2.process_count) == (1, N)
    assert [b.to_bytes() for b in resumed2] == shard[2:]

    # a per-process cursor only positions its own shard
    with pytest.raises(ValueError, match="names shard"):
        ResumableDataset.from_cursor(factory, cur,
                                     process_index=0, process_count=N)
    with pytest.raises(ValueError, match="outside group"):
        ResumableDataset(factory, seed=5, process_index=2, process_count=2)


# --- PERSIA_MULTIHOST_CACHE negotiate-down ---------------------------------


def test_multihost_cache_negotiate_down_modes(monkeypatch):
    """`off` (default) disables the cache LOUDLY and lets the run
    continue on the PS-only hybrid path; `refuse` preserves the
    historic hard error; anything else is a config typo and raises."""
    from persia_tpu.ctx import TrainCtx

    def fresh():
        ctx = TrainCtx.__new__(TrainCtx)
        ctx.device_cache_capacity = 64
        return ctx

    monkeypatch.delenv("PERSIA_MULTIHOST_CACHE", raising=False)
    ctx = fresh()
    assert ctx._negotiate_multihost_cache() is True  # default == off
    assert ctx.device_cache_capacity == 0

    monkeypatch.setenv("PERSIA_MULTIHOST_CACHE", "refuse")
    ctx = fresh()
    assert ctx._negotiate_multihost_cache() is False
    assert ctx.device_cache_capacity == 64  # untouched: caller raises

    monkeypatch.setenv("PERSIA_MULTIHOST_CACHE", "bogus")
    with pytest.raises(ValueError, match="PERSIA_MULTIHOST_CACHE"):
        fresh()._negotiate_multihost_cache()


# --- 2-trainer ServiceCtx group --------------------------------------------


def test_two_trainer_group_counting_identity(tmp_path):
    """Two supervised trainer processes share one worker/PS tier: each
    writes its own .p<i> result file, trains exactly its round-robin
    half of the global stream, ships with its process label, and the
    per-sign update counts SUMMED across the group match the 1-process
    expectation exactly."""
    import urllib.request

    from persia_tpu.config import EmbeddingSchema, uniform_slots
    from persia_tpu.service.helper import ServiceCtx
    from persia_tpu.service.trainer_service import batch_draws, sign_pool
    from persia_tpu.service_discovery import get_fleet_targets

    dim, n_feats, seed, pool_size = 8, 2, 3, 1024
    steps, bs = 8, 32
    result_file = str(tmp_path / "result.json")
    trainer_args = [
        "--num-workers", "1", "--steps", str(steps),
        "--batch-size", str(bs), "--n-feats", str(n_feats),
        "--seed", str(seed), "--pool-size", str(pool_size),
        "--result-file", result_file]
    schema = EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{i}" for i in range(n_feats)], dim=dim))

    with ServiceCtx(schema, n_workers=1, n_ps=2, supervise_trainer=True,
                    trainer_args=trainer_args, n_trainers=2,
                    trainer_max_restarts=0, http_all=True) as svc:
        assert svc.wait_trainer_done(timeout=240.0) == 0
        # per-process ship labels on the shared worker tier
        ship_counts = {}
        for t in get_fleet_targets(svc.coordinator_addr):
            if t["role"] != "embedding-worker" or not t.get("http_addr"):
                continue
            with urllib.request.urlopen(
                    f"http://{t['http_addr']}/healthz", timeout=5) as r:
                ship_counts = json.loads(r.read()).get("ship_counts", {})
        assert ship_counts == {"p0": steps // 2, "p1": steps // 2}

        # summed identity: union of the two shard streams == the one
        # global stream, each sign updated exactly as often as drawn
        pool = sign_pool(pool_size)
        expected = np.zeros(len(pool), np.int64)
        for k in range(steps):
            draws = batch_draws(pool, seed, k, bs, n_feats)
            np.add.at(expected,
                      np.searchsorted(pool, np.concatenate(draws)), 1)
        rows = svc.remote_worker().lookup_signs(pool, dim)
        applied = -rows.sum(axis=1) / dim
        np.testing.assert_allclose(applied, expected, atol=1e-3)

    # group members share argv: each claims its own suffixed file
    results = []
    for i in range(2):
        with open(f"{result_file}.p{i}") as f:
            results.append(json.load(f))
    assert [r["process_index"] for r in results] == [0, 1]
    assert all(r["process_count"] == 2 for r in results)
    assert sum(r["ships"] for r in results) == steps
    assert not os.path.exists(result_file)  # bare path is 1-process only


def _counting_group(steps, bs, extra=()):
    """Schema, trainer argv and the identity's inputs for one counting
    trainer group over the deterministic stream."""
    from persia_tpu.config import EmbeddingSchema, uniform_slots

    dim, n_feats, seed, pool_size = 8, 2, 3, 2048
    schema = EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{i}" for i in range(n_feats)], dim=dim))
    args = ["--num-workers", "1", "--steps", str(steps),
            "--batch-size", str(bs), "--n-feats", str(n_feats),
            "--seed", str(seed), "--pool-size", str(pool_size), *extra]

    def assert_identity(tag, worker):
        from persia_tpu.service.trainer_service import sign_pool
        from tests.fleet_support import (
            applied_counts,
            assert_counting_identity,
            expected_counts,
        )

        pool = sign_pool(pool_size)
        assert_counting_identity(
            tag, pool, expected_counts(pool, seed, steps, bs, n_feats),
            applied_counts(worker, pool, dim))

    return schema, args, assert_identity


def _group_results(result_file, n=2):
    out = []
    for i in range(n):
        with open(f"{result_file}.p{i}") as f:
            out.append(json.load(f))
    return sorted(out, key=lambda r: r["process_index"])


def test_two_trainer_group_over_a_real_mesh_with_dense_rider(tmp_path):
    """P=2 over a real jax.distributed CPU mesh (rendezvous through the
    coordinator KV) with the int8-EF dense all-reduce rider every 4
    local steps: the per-sign counting identity summed across the group
    is exact, both members allgather the same group ship count and see
    the same mesh, the rider ran the same number of rounds on both and
    the replicas agree on the synced loss."""
    from persia_tpu.service.helper import ServiceCtx
    from tests.fleet_support import time_limit

    steps, bs = 16, 32
    result_file = str(tmp_path / "result.json")
    schema, args, assert_identity = _counting_group(
        steps, bs, ("--jax-mesh", "--dense-sync-every", "4",
                    "--result-file", result_file))
    with time_limit(300, "trainer group over a mesh"), \
            ServiceCtx(schema, n_workers=1, n_ps=2,
                       supervise_trainer=True, trainer_args=args,
                       n_trainers=2, trainer_env={"JAX_PLATFORMS": "cpu"},
                       trainer_max_restarts=0, http_all=True) as svc:
        assert svc.wait_trainer_done(timeout=240.0) == 0
        assert_identity("group:mesh", svc.remote_worker())
    r0, r1 = _group_results(result_file)
    assert r0["ships"] + r1["ships"] == steps
    for r in (r0, r1):
        assert r["group_ships"] == steps
        assert r["mesh_shape"] and r["mesh_shape"] == r0["mesh_shape"]
    assert r0["dense_syncs"] and r0["dense_syncs"] == r1["dense_syncs"]
    assert abs(r0["dense_loss"] - r1["dense_loss"]) <= 1e-5


def test_live_reshard_under_a_running_trainer_group(tmp_path):
    """Shrink the PS tier 4→3 while both trainers stream lookups and
    updates; the migration overlaps live traffic (the group is still
    running when the cutover lands) and the summed counting identity
    shows zero lost updates."""
    import urllib.request

    from persia_tpu.reshard import ReshardController
    from persia_tpu.routing import RoutingTable
    from persia_tpu.service.helper import ServiceCtx
    from persia_tpu.service.ps_service import PsClient
    from persia_tpu.service_discovery import get_fleet_targets
    from tests.fleet_support import time_limit, wait_until

    schema, args, assert_identity = _counting_group(
        32, 32, ("--step-delay", "0.15",
                 "--result-file", str(tmp_path / "result.json")))
    with time_limit(300, "reshard under a trainer group"), \
            ServiceCtx(schema, n_workers=1, n_ps=4,
                       supervise_trainer=True, trainer_args=args,
                       n_trainers=2, trainer_max_restarts=0,
                       http_all=True) as svc:

        def mid_stream():
            for t in get_fleet_targets(svc.coordinator_addr):
                if t["role"] != "nn-worker":
                    continue
                try:
                    with urllib.request.urlopen(
                            f"http://{t['http_addr']}/healthz",
                            timeout=1.0) as r:
                        if json.loads(r.read()).get("step", 0) >= 2:
                            return True
                except Exception:  # noqa: BLE001 — sidecar not up yet
                    pass
            return False

        wait_until(mid_stream, 120, "no trainer got past step 2",
                   interval=0.2)
        assert not svc.trainer_done, \
            "group finished before the migration could overlap it"
        rw = svc.remote_worker()
        ctrl = ReshardController(
            [PsClient(a, circuit_breaker=False) for a in svc.ps_addrs],
            RoutingTable.uniform(4), workers=[rw],
            replay_settle_rows=64, drain_sec=0.25)
        t3 = ctrl.reshard_to(3)
        assert not svc.trainer_done, "the cutover did not land under load"
        assert svc.wait_trainer_done(timeout=240.0) == 0
        ctrl.finalize(drain_sec=0.0)
        assert t3.num_replicas == 3
        assert_identity("group:reshard", rw)


def test_one_process_wire_is_untouched_by_group_support():
    """The multi-process plumbing is byte-invisible when unused: K
    train cycles through the default (unlabeled) RemoteEmbeddingWorker
    cost exactly 3 RPCs a cycle (put_batch + lookup + update), the
    update payload is byte-identical to the historic
    ``{ref_id, loss_scale}`` meta encoding, and the worker attributes
    every shipment to the unlabeled ("") process. A labeled control run
    shows the label changes attribution, not the RPC count."""
    from persia_tpu.config import EmbeddingSchema, uniform_slots
    from persia_tpu.data.batch import IDTypeFeature
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service import serialization as ser
    from persia_tpu.service.trainer_service import ARM_INIT, ARM_OPT
    from persia_tpu.service.worker_service import (
        RemoteEmbeddingWorker,
        WorkerService,
    )
    from persia_tpu.worker.worker import EmbeddingWorker

    dim, n_feats, cycles, bs = 8, 2, 6, 32
    schema = EmbeddingSchema(slots_config=uniform_slots(
        [f"slot_{i}" for i in range(n_feats)], dim=dim))
    rng = np.random.default_rng(11)

    def run(label):
        svc = WorkerService(
            EmbeddingWorker(schema, [EmbeddingHolder(capacity=100_000)]),
            http_port=None)
        svc.server.serve_background()
        try:
            rw = RemoteEmbeddingWorker([svc.addr])
            rw.process_label = label
            rw.configure_parameter_servers(*ARM_INIT)
            rw.register_optimizer(ARM_OPT)
            captured = []
            cli = rw._clients[rw.addrs[0]]
            orig_call = cli.call

            def spy(method, payload=b"", **kw):
                if method == "update_gradients":
                    captured.append(payload)
                return orig_call(method, payload, **kw)

            cli.call = spy
            served0 = svc.server.health()["served_rpcs"]
            for _ in range(cycles):
                feats = [IDTypeFeature(
                    f"slot_{i}",
                    [rng.integers(0, 1 << 30, bs, dtype=np.uint64)])
                    for i in range(n_feats)]
                ref, out = rw.lookup_direct_training(feats)
                grads = {k: np.ones_like(v.embeddings)
                         for k, v in out.items()}
                rw.update_gradients(ref, grads)
            return (svc.server.health()["served_rpcs"] - served0,
                    dict(svc._health().get("ship_counts", {})),
                    captured[-1], ref, grads)
        finally:
            svc.stop()

    delta_u, ships_u, payload_u, ref, grads = run(None)
    assert payload_u == ser.pack_gradients(
        grads, {"ref_id": ref[1], "loss_scale": 1.0})
    delta_l, ships_l, *_ = run("p0")
    assert delta_u == delta_l == 3 * cycles
    assert ships_u == {"": cycles} and ships_l == {"p0": cycles}
