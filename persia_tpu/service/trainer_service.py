"""Supervised trainer driver — the nn-worker leg of whole-job crash
safety (reference: persia/e2e trainer entrypoints; kill matrix in
tests/test_chaos_job.py).

This binary is what ``ServiceCtx(supervise_trainer=True)`` respawns
after a trainer SIGKILL. It runs the counting workload the chaos cells
gate on (zero-init embeddings + sgd lr=1 + unit gradients, so the
per-sign identity ``applied == -count`` holds elementwise), takes
coordinated job snapshots every ``--snapshot-interval`` steps via
:func:`persia_tpu.snapshot.snapshot_job`, and on start resumes from the
newest COMPLETE snapshot: roll the PS stores back to the snapshot
(``worker.load`` wipes post-snapshot updates), then replay the
deterministic batch stream from the saved cursor. Every batch is a pure
function of ``(seed, step)``, so replay re-derives the wiped updates
exactly once and the counting identity stays EXACT across any number of
kills.

Chaos injection (``--die-at``) SIGKILLs this process at a named point:

* ``mid_step``          — between lookup and gradient update
* ``mid_snapshot``      — inside snapshot_job, after payloads, before
                          the manifest (leaves a torn snapshot the
                          resume path must refuse and fall back past)
* ``between_snapshots`` — at a step boundary away from the cadence

A marker file under the snapshot dir makes each kill fire exactly once
across incarnations. On completing ``--steps`` the driver writes
``--result-file`` atomically and exits 0 (supervisor treats that as
done, not a crash).

Multi-process trainer group (``--process-index``/``--process-count``,
the pod-scale hybrid): N copies of this driver run against ONE shared
worker/PS tier. Each copy shards the deterministic global batch stream
by round-robin (``ResumableDataset`` process sharding: batch ``i``
belongs to process ``i % N``), runs its own lookup/update fan-out (so
RPC concurrency scales with trainer hosts instead of serializing
through process 0), labels its backward shipments ``p<index>`` for
per-process fleet attribution, and — with ``--jax-mesh`` — rendezvouses
a real ``jax.distributed`` global mesh through the fleet coordinator's
KV store (process 0 binds a port and publishes ``host:port`` under
``PERSIA_TRAINER_RENDEZVOUS_KEY``; the rest ``wait_kv`` it), then syncs
a dense tower through the int8-EF all-reduce every
``--dense-sync-every`` local steps.

Multi-process crash-safety is CURSOR-ONLY: each process checkpoints its
shard cursor (``cursor_p<i>.json``) and a restart resumes its own shard
position, but there is no coordinated PS rollback — replayed tail steps
double-apply (at-least-once). Exact-identity kill recovery stays a
single-process guarantee (ARCHITECTURE.md "Multi-host hybrid").
"""

import argparse
import json
import os
import signal
import time

import numpy as np

from persia_tpu import knobs, obs_http, tracing
from persia_tpu import snapshot as _snapshot
from persia_tpu.data.batch import IDTypeFeature
from persia_tpu.data.dataloader import ResumableDataset
from persia_tpu.logger import get_default_logger
from persia_tpu.service.coordinator import (
    ROLE_TRAINER,
    ROLE_WORKER,
    CoordinatorClient,
)
from persia_tpu.service.worker_service import RemoteEmbeddingWorker
from persia_tpu.storage import PersiaPath

_logger = get_default_logger(__name__)

# Counting arm: zero-init + sgd lr=1 + unit grads -> row == -count.
ARM_INIT = ("bounded_uniform", {"lower": 0.0, "upper": 0.0}, 1.0, 1e9, False)
ARM_OPT = {"type": "sgd", "lr": 1.0, "wd": 0.0}

DIE_POINTS = ("none", "mid_step", "mid_snapshot", "between_snapshots")


def sign_pool(pool_size: int) -> np.ndarray:
    """The fixed sign universe every incarnation draws from — identical
    to the chaos harness's ledger pool so the bench can regenerate the
    exact expected per-sign counts."""
    return np.unique(np.random.default_rng(7).integers(
        0, 1 << 40, pool_size, dtype=np.uint64))


def batch_draws(pool: np.ndarray, seed: int, step: int,
                batch_size: int, n_feats: int):
    """Batch ``step`` of the stream — a pure function of (seed, step)."""
    rng = np.random.default_rng([seed, step])
    return [rng.choice(pool, size=batch_size) for _ in range(n_feats)]


def _die_now():
    # SIGKILL, not sys.exit: the point is an unclean death the
    # supervisor must detect and recover from
    os.kill(os.getpid(), signal.SIGKILL)


def _mesh_up(coord: CoordinatorClient, args):
    """Bring up the ``jax.distributed`` global mesh for this trainer
    group, rendezvousing through the fleet coordinator's KV store:
    process 0 picks a free port and publishes ``host:port`` under
    ``--rendezvous-key``; everyone else ``wait_kv``s it. Returns
    ``(jax, mesh)``. Must run before ANY other jax backend init."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # CPU-mesh dev/CI recipe: pin the CPU platform without touching
        # the backend (jax.distributed.initialize must be its first init)
        from persia_tpu.utils import force_cpu_platform

        force_cpu_platform(1, verify=False)
    import jax  # noqa: F401  (deferred: heavyweight, mesh cells only)

    from persia_tpu.utils import enable_compile_cache

    enable_compile_cache()
    from persia_tpu.distributed import DistributedOption

    if args.process_count == 1:
        opt = DistributedOption(multihost=False)
        return jax, opt.initialize()
    if args.process_index == 0:
        from persia_tpu.utils import find_free_port

        addr = f"{args.rendezvous_host}:{find_free_port()}"
        coord.kv_put(args.rendezvous_key, addr.encode())
    else:
        addr = coord.wait_kv(
            args.rendezvous_key,
            timeout=knobs.get("PERSIA_TRAINER_RENDEZVOUS_TIMEOUT_SEC"),
        ).decode()
    opt = DistributedOption(
        multihost=True, coordinator_address=addr,
        num_processes=args.process_count, process_id=args.process_index)
    mesh = opt.initialize()
    _logger.info("trainer mesh up: process %d/%d via %s",
                 args.process_index, args.process_count, addr)
    return jax, mesh


def _dense_rider(jax, mesh, process_count: int, seed: int):
    """Tiny dense tower riding the sparse stream: every call runs one
    int8-EF compressed all-reduce step over the GLOBAL mesh — the
    synchronous data-parallel leg of the hybrid, interleaved with the
    async PS data plane. Returns ``sync(round_no, pid) -> loss``."""
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from persia_tpu.models import DNN
    from persia_tpu.parallel.train import (
        create_train_state,
        init_ef_state,
        make_packed_train_step_ddp,
    )

    n_local = jax.local_device_count()
    bs_local = 2 * n_local
    rows = process_count * bs_local
    slot_dims = [8, 8]
    model = DNN()
    opt = optax.sgd(0.1)
    state = create_train_state(
        model, opt, jax.random.key(seed),
        [jnp.zeros((rows, 5))],
        [jnp.zeros((rows, 8)), jnp.zeros((rows, 8))])
    step_fn = make_packed_train_step_ddp(model, opt, slot_dims, mesh,
                                         grad_reduce_dtype="int8_ef")
    sharding = NamedSharding(mesh, P("data"))
    holder = {"state": state, "ef": init_ef_state(state.params, mesh)}

    def shard(local, width):
        return jax.make_array_from_process_local_data(
            sharding, local, (rows, width))

    def sync(round_no: int, pid: int) -> float:
        # inputs are a pure function of (seed, round, pid): each process
        # contributes ITS shard, like real per-host batches
        rng = np.random.default_rng([seed, round_no, pid])
        non_id = jnp.asarray(
            rng.normal(size=(bs_local, 5)).astype(np.float32))
        emb = jnp.asarray(
            rng.normal(size=(bs_local, 16)).astype(np.float32),
            jnp.bfloat16)
        label = jnp.asarray(
            rng.integers(0, 2, size=(bs_local, 1)).astype(np.float32))
        (holder["state"], loss, _g, _p, holder["ef"]) = step_fn(
            holder["state"], [shard(non_id, 5)], shard(emb, 16),
            shard(label, 1), holder["ef"])
        return float(loss)

    return sync


def main(argv=None):
    p = argparse.ArgumentParser(description="persia_tpu chaos trainer driver")
    p.add_argument("--coordinator", required=True)
    p.add_argument("--num-workers", type=int, default=1)
    p.add_argument("--snapshot-dir", default=None)
    p.add_argument("--snapshot-interval", type=int,
                   default=knobs.get("PERSIA_SNAPSHOT_INTERVAL_STEPS"))
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--n-feats", type=int, default=2)
    p.add_argument("--seed", type=int, default=3)
    p.add_argument("--pool-size", type=int, default=8192)
    p.add_argument("--die-at", choices=DIE_POINTS, default="none")
    p.add_argument("--die-step", type=int, default=-1)
    p.add_argument("--result-file", default=None)
    p.add_argument("--step-delay", type=float, default=0.0)
    # --- multi-process trainer group -------------------------------------
    p.add_argument("--process-index", type=int,
                   default=knobs.get("PERSIA_PROCESS_INDEX"))
    p.add_argument("--process-count", type=int,
                   default=knobs.get("PERSIA_PROCESS_COUNT"))
    p.add_argument("--workload", default="counting",
                   help="'counting' (chaos/identity arm) or a zoo "
                        "scenario name (dlrm/seqrec/multitask): same "
                        "lookup/update data plane, production-shaped "
                        "slot layout")
    p.add_argument("--jax-mesh", action="store_true",
                   help="rendezvous a jax.distributed global mesh over "
                        "the coordinator KV store")
    p.add_argument("--dense-sync-every", type=int, default=0,
                   help="run the int8-EF dense all-reduce rider every "
                        "K local steps (needs --jax-mesh)")
    p.add_argument("--rendezvous-key",
                   default=knobs.get("PERSIA_TRAINER_RENDEZVOUS_KEY"))
    p.add_argument("--rendezvous-host", default="127.0.0.1")
    obs_http.add_http_args(p)
    args = p.parse_args(argv)
    if not 0 <= args.process_index < args.process_count:
        p.error(f"--process-index {args.process_index} outside group "
                f"of {args.process_count}")
    multi = args.process_count > 1
    if args.dense_sync_every and not args.jax_mesh:
        p.error("--dense-sync-every needs --jax-mesh")
    if args.dense_sync_every and args.steps % args.process_count:
        # the rider is a COLLECTIVE: every process must reach the same
        # number of local sync rounds or the group deadlocks
        p.error("--dense-sync-every needs --steps divisible by "
                "--process-count")

    tracing.set_service_name("trainer")
    status = {"model_manager_status": "Initializing", "step": 0,
              "resumed_from": None, "process_index": args.process_index,
              "process_count": args.process_count, "mesh_shape": None,
              "ships": 0, "workload": args.workload}

    # process-labeled gauges: the fleet history keys series by
    # (service, metric, labels), so every group member's step/ship
    # progress is a distinct /fleet/history series
    from persia_tpu import metrics as _metrics

    _lbl = {"process": f"p{args.process_index}"}
    g_step = _metrics.default_registry().gauge(
        "trainer_step", labels=_lbl,
        help_text="local train steps completed by this trainer process")
    g_ships = _metrics.default_registry().gauge(
        "trainer_ships_total", labels=_lbl,
        help_text="gradient shipments sent by this trainer process")

    def health_fn():
        return dict(status, service="trainer")

    http = obs_http.maybe_start("127.0.0.1", obs_http.port_from_args(args),
                                health_fn)
    obs_http.write_addr_file_from_args(http, args)

    coord = CoordinatorClient(args.coordinator)

    mesh = jax = None
    if args.jax_mesh:
        # BEFORE any other work that could touch jax: distributed init
        # must be the first backend init in the process
        jax, mesh = _mesh_up(coord, args)
        status["mesh_shape"] = "x".join(
            str(d) for d in mesh.devices.shape)

    # the trainer registers like every other tier so /fleet/status shows
    # the whole co-scheduled group (role prefix "trainer", one row per
    # process_index); the sidecar addr doubles as the display addr
    trainer_addr = http.addr if http is not None else f"pid:{os.getpid()}"
    coord.register(ROLE_TRAINER, args.process_index, trainer_addr,
                   http_addr=http.addr if http is not None else None)

    addrs = coord.wait_members(ROLE_WORKER, args.num_workers, timeout=120)
    worker = RemoteEmbeddingWorker(addrs)
    if multi:
        # label backward shipments so the worker tier can attribute
        # per-process data-plane traffic; single-process trainers send
        # no label (wire byte-identical)
        worker.process_label = f"p{args.process_index}"
    # arm BEFORE the readiness wait: a PS is not "serving" until it is
    # configured and has an optimizer. In a group every process arms —
    # configure/register are idempotent on an already-armed PS.
    worker.configure_parameter_servers(*ARM_INIT)
    worker.register_optimizer(ARM_OPT)
    worker.wait_for_serving(timeout=120)

    pool = sign_pool(args.pool_size)
    die_step = args.die_step
    die_marker = None
    die_at = args.die_at
    if args.snapshot_dir and die_at != "none":
        die_marker = os.path.join(
            args.snapshot_dir, f".die_{die_at}_{die_step}")
        if os.path.exists(die_marker):
            die_at = "none"  # this kill already fired in a past life

    def arm_kill():
        # marker BEFORE the kill: if we die mid-write the worst case is
        # one extra kill, never an unkillable loop
        if die_marker:
            PersiaPath(die_marker).write_bytes_atomic(b"1")

    # --- resume -----------------------------------------------------------
    # single-process: roll the whole job back to the newest complete
    # snapshot (PS load wipes post-snapshot updates; deterministic
    # replay re-derives them exactly once). Multi-process: CURSOR-ONLY —
    # each process resumes its own shard position from cursor_p<i>.json;
    # no PS rollback, so replayed tail steps double-apply
    # (at-least-once; see module docstring).
    start = 0
    cursor_file = None
    if args.snapshot_dir and multi:
        cursor_file = os.path.join(
            args.snapshot_dir, f"cursor_p{args.process_index}.json")
        if os.path.exists(cursor_file):
            with open(cursor_file) as f:
                cur = json.load(f)
            start = int(cur.get("consumed", 0))
            status["resumed_from"] = os.path.basename(cursor_file)
            _logger.info("resumed shard %d/%d from %s at local step %d",
                         args.process_index, args.process_count,
                         cursor_file, start)
    elif args.snapshot_dir:
        found = _snapshot.latest_snapshot(args.snapshot_dir)
        if found is not None:
            snap, manifest = found
            status["model_manager_status"] = "Loading"
            worker.load(snap)  # PS load is clear=True: post-snap updates wiped
            cur = manifest.get("cursor") or {}
            start = int(cur.get("consumed", 0))
            status["resumed_from"] = os.path.basename(snap)
            _logger.info("resumed from %s at step %d", snap, start)

    # --- workload: one GLOBAL deterministic stream of --steps batches,
    # round-robin-sharded across the group by ResumableDataset
    if args.workload == "counting":
        def factory(seed):
            for k in range(args.steps):
                draws = batch_draws(pool, seed, k, args.batch_size,
                                    args.n_feats)
                yield [IDTypeFeature(f"slot_{i}", [d])
                       for i, d in enumerate(draws)]

        def feats_of(item):
            return item
    else:
        from persia_tpu.workloads.registry import get_scenario

        scenario = get_scenario(args.workload, smoke=True, seed=args.seed)

        def factory(seed):
            return scenario.batches(args.steps * args.batch_size,
                                    args.batch_size, seed=seed)

        def feats_of(item):
            return item.id_type_features

    ds = ResumableDataset(factory, seed=args.seed, start=start,
                          process_index=args.process_index,
                          process_count=args.process_count)

    dense_sync = None
    dense_syncs, dense_loss = 0, None
    if args.dense_sync_every:
        dense_sync = _dense_rider(jax, mesh, args.process_count, args.seed)

    status["model_manager_status"] = "Training"
    ships = 0
    step = start  # LOCAL step counter (this shard's batches)
    t_loop = time.monotonic()
    for item in ds:
        feats = feats_of(item)
        if die_at == "between_snapshots" and step == die_step:
            arm_kill()
            _die_now()
        # nested spans: the supervisor's postmortem validator requires a
        # parent->child chain in the flight ring, and the client RPC
        # layer emits none of its own
        with tracing.span("trainer/step", root=True):
            with tracing.span("trainer/lookup"):
                ref, out = worker.lookup_direct_training(feats)
            if die_at == "mid_step" and step == die_step:
                arm_kill()
                _die_now()
            with tracing.span("trainer/update"):
                worker.update_gradients(ref, {
                    k: np.ones_like(v.embeddings) for k, v in out.items()})
        ships += 1
        step += 1
        status["step"] = step
        status["ships"] = ships
        g_step.set(step)
        g_ships.set(ships)
        if dense_sync is not None and (step - start) % args.dense_sync_every == 0:
            with tracing.span("trainer/dense_sync"):
                dense_loss = dense_sync(dense_syncs, args.process_index)
            dense_syncs += 1
            status["dense_loss"] = dense_loss
        if args.snapshot_dir and step % args.snapshot_interval == 0:
            if multi:
                PersiaPath(cursor_file).write_bytes_atomic(
                    json.dumps(ds.cursor(trained=step - start)).encode())
            else:
                pre = None
                if die_at == "mid_snapshot" and step >= max(die_step, 1):
                    def pre(_snap):  # noqa: E306
                        arm_kill()
                        _die_now()
                status["model_manager_status"] = "Dumping"
                _snapshot.snapshot_job(
                    args.snapshot_dir, worker,
                    cursor=ds.cursor(trained=step - start),
                    step=step, pre_manifest=pre)
                status["model_manager_status"] = "Training"
        if args.step_delay:
            time.sleep(args.step_delay)
    elapsed = time.monotonic() - t_loop

    # final snapshot/cursor so the full run is durable, then report
    if args.snapshot_dir:
        if multi:
            PersiaPath(cursor_file).write_bytes_atomic(
                json.dumps(ds.cursor(trained=step - start)).encode())
        else:
            _snapshot.snapshot_job(args.snapshot_dir, worker,
                                   cursor=ds.cursor(trained=step - start),
                                   step=step)

    group_ships = None
    if mesh is not None and multi:
        # cross-process proof the whole group's backward traffic landed:
        # allgather each shard's ship count over the global mesh
        import jax.numpy as jnp
        from jax.experimental import multihost_utils

        g = multihost_utils.process_allgather(jnp.array([float(ships)]))
        group_ships = int(g.sum())

    status["model_manager_status"] = "Done"
    if args.result_file:
        # group members share argv (one --result-file for the whole
        # trainer group), so each process claims its own suffixed file;
        # single-process keeps the historic bare path
        result_file = (f"{args.result_file}.p{args.process_index}"
                       if multi else args.result_file)
        PersiaPath(result_file).write_bytes_atomic(json.dumps({
            "steps": step, "seed": args.seed, "pool_size": args.pool_size,
            "batch_size": args.batch_size, "n_feats": args.n_feats,
            "resumed_from": status["resumed_from"],
            "process_index": args.process_index,
            "process_count": args.process_count,
            "workload": args.workload,
            "elapsed_sec": elapsed,
            "samples": (step - start) * args.batch_size,
            "ships": ships,
            "group_ships": group_ships,
            "mesh_shape": status["mesh_shape"],
            "dense_syncs": dense_syncs,
            "dense_loss": dense_loss,
        }).encode())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
