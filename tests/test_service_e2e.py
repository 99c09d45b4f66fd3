"""Multi-process cluster integration tests over real sockets
(reference: test/test_ctx.py:66-172 + persia/helper.py).

Spawns coordinator + parameter-server + embedding-worker subprocesses and
drives send -> lookup -> train -> update round trips from this process.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples" / "adult_income"))

import optax

from data_generator import NUM_SLOTS, batches  # noqa: E402

from persia_tpu.config import EmbeddingSchema, uniform_slots  # noqa: E402
from persia_tpu.ctx import DataCtx, TrainCtx  # noqa: E402
from persia_tpu.data.batch import IDTypeFeature  # noqa: E402
from persia_tpu.data.dataloader import DataLoader, StreamingDataset  # noqa: E402
from persia_tpu.embedding import EmbeddingConfig  # noqa: E402
from persia_tpu.embedding.optim import Adagrad  # noqa: E402
from persia_tpu.models import DNN  # noqa: E402
from persia_tpu.service.dataflow import DataflowClient, DataflowReceiver  # noqa: E402
from persia_tpu.service.helper import ServiceCtx  # noqa: E402



def _schema():
    return EmbeddingSchema(
        slots_config=uniform_slots(
            [f"slot_{s}" for s in range(NUM_SLOTS)], dim=8
        )
    )


@pytest.fixture(scope="module")
def cluster():
    with ServiceCtx(_schema(), n_workers=2, n_ps=2) as svc:
        yield svc


def test_remote_lookup_update_round_trip(cluster):
    w = cluster.remote_worker()
    w.configure_parameter_servers(
        "bounded_uniform", {"lower": -0.1, "upper": 0.1}, 1.0, 10.0)
    w.register_optimizer({"type": "sgd", "lr": 0.1, "wd": 0.0})
    feats = [IDTypeFeature("slot_0", [np.array([1, 2], np.uint64)]),
             IDTypeFeature("slot_1", [np.array([3], np.uint64)])]
    ref, result = w.lookup_direct_training(feats)
    emb0 = result["slot_0"].embeddings
    assert emb0.shape == (1, 8)
    assert not (emb0 == 0).all()
    w.update_gradients(ref, {
        "slot_0": np.ones((1, 8), np.float32),
        "slot_1": np.ones((1, 8), np.float32),
    })
    again = w.lookup_direct(feats, training=False)
    # both signs in sample 0 got grad 1.0 -> each moved by -lr*1
    np.testing.assert_allclose(
        again["slot_0"].embeddings, emb0 - 2 * 0.1, atol=1e-5)
    assert w.staleness == 0


def test_remote_training_via_train_ctx(cluster):
    """TrainCtx drives the remote cluster exactly like local mode."""
    schema = _schema()
    worker = cluster.remote_worker()
    ctx = TrainCtx(
        model=DNN(),
        dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=1e-2),
        schema=schema,
        worker=worker,
        embedding_config=EmbeddingConfig(emb_initialization=(-0.05, 0.05)),
    )
    losses = []
    with ctx:
        for b in batches(10 * 128, 128, seed=21):
            loss, _ = ctx.train_step(b)
            losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert len(losses) == 10


def test_four_role_dataflow(cluster):
    """data-loader -> worker + trainer dataflow -> DataLoader pipeline."""
    schema = _schema()
    worker = cluster.remote_worker()
    receiver = DataflowReceiver()
    try:
        # trainer side
        ctx = TrainCtx(
            model=DNN(),
            dense_optimizer=optax.adam(1e-3),
            embedding_optimizer=Adagrad(lr=1e-2),
            schema=schema,
            worker=worker,
            embedding_config=EmbeddingConfig(),
        )
        with ctx:
            # data-loader side (same process here; separate role in prod)
            with DataCtx(dataflow=DataflowClient(
                cluster.remote_worker(), [receiver.addr]
            )) as dctx:
                for b in batches(6 * 64, 64, seed=31):
                    dctx.send_data(b)
                dctx.dataflow.send_eos()

            loader = DataLoader(StreamingDataset(receiver), num_workers=2,
                                embedding_staleness=2)
            count = 0
            for lb in loader:
                assert lb.batch.remote_ref is not None
                loss, _ = ctx.train_step(lb)
                count += 1
            assert count == 6
            assert worker.staleness == 0
    finally:
        receiver.close()


def test_ps_dump_load_over_rpc(cluster, tmp_path):
    from persia_tpu.service.ps_service import PsClient

    ps = PsClient(cluster.ps_addrs[0])
    before = len(ps)
    assert before > 0  # earlier tests created entries
    path = str(tmp_path / "shard.psd")
    ps.dump_file(path)
    assert ps.model_manager_status() == "Idle"
    ps.load_file(path)
    assert len(ps) == before


def test_crash_detection():
    with ServiceCtx(_schema(), n_workers=1, n_ps=1) as svc:
        # murder a PS; the monitor should tear the group down
        ps_proc = next(p for p in svc.procs
                       if getattr(p, "_persia_name", "").startswith("ps"))
        ps_proc.kill()
        import time

        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not svc.crashed:
            time.sleep(0.2)
        assert svc.crashed


def test_inference_server_end_to_end(cluster):
    """PersiaBatch bytes -> InferenceServer -> predictions (the serving
    path, reference serve_handler.py)."""
    import jax

    from persia_tpu.parallel.train import create_train_state
    from persia_tpu.serving import InferenceClient, InferenceServer

    schema = _schema()
    model = DNN()
    # build a state from one example batch's shapes
    b = next(iter(batches(64, 64, seed=77, requires_grad=False)))
    worker = cluster.remote_worker()
    lookup = worker.lookup_direct(b.id_type_features, training=False)
    from persia_tpu.ctx import EmbeddingCtx

    ectx = EmbeddingCtx(model=model, schema=schema, worker=worker)
    non_id, emb_inputs, _ = ectx.prepare_features(b, lookup)
    state = create_train_state(model, optax.adam(1e-3), jax.random.key(0),
                               non_id, emb_inputs)

    server = InferenceServer(model, state, schema,
                             worker_addrs=cluster.worker_addrs)
    server.serve_background()
    try:
        client = InferenceClient(server.addr)
        assert client.healthy()
        preds = client.predict(b)
        assert preds.shape == (64, 1)
        assert np.isfinite(preds).all()
        # deterministic across calls
        np.testing.assert_array_equal(preds, client.predict(b))
    finally:
        server.server.stop()


def test_native_ps_cluster_end_to_end():
    """Full cluster with the C++ persia-embedding-ps binary as the PS tier."""
    with ServiceCtx(_schema(), n_workers=1, n_ps=2, native_ps=True,
                    ps_capacity=100_000, ps_num_shards=4) as svc:
        w = svc.remote_worker()
        w.configure_parameter_servers(
            "bounded_uniform", {"lower": -0.1, "upper": 0.1}, 1.0, 10.0)
        w.register_optimizer({"type": "adagrad", "lr": 0.01})
        ctx = TrainCtx(
            model=DNN(),
            dense_optimizer=optax.adam(1e-3),
            embedding_optimizer=Adagrad(lr=1e-2),
            schema=_schema(),
            worker=w,
            embedding_config=EmbeddingConfig(),
        )
        losses = []
        with ctx:
            for b in batches(6 * 128, 128, seed=41):
                loss, _ = ctx.train_step(b)
                losses.append(float(loss))
        assert np.isfinite(losses).all() and len(losses) == 6
        from persia_tpu.service.ps_service import PsClient

        total = sum(len(PsClient(a)) for a in svc.ps_addrs)
        assert total > 0


def test_incremental_update_through_services(tmp_path):
    """Train-side PS emits delta packets (global config), infer-side holder
    hot-loads them — the online-serving sync loop at cluster level."""
    import yaml

    from persia_tpu.inc_update import IncrementalUpdateLoader
    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service.ps_service import PsClient

    gc_path = tmp_path / "global.yml"
    inc_dir = tmp_path / "inc"
    yaml.safe_dump({
        "common_config": {"job_type": "Train"},
        "embedding_parameter_server_config": {
            "capacity": 100000,
            "num_hashmap_internal_shards": 2,
            "enable_incremental_update": True,
            "incremental_buffer_size": 10,
            "incremental_dir": str(inc_dir),
        },
    }, gc_path.open("w"))
    with ServiceCtx(_schema(), n_workers=1, n_ps=1,
                    global_config_path=str(gc_path)) as svc:
        ps = PsClient(svc.ps_addrs[0])
        ps.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
        ps.register_optimizer({"type": "sgd", "lr": 0.1})
        signs = np.arange(1, 40, dtype=np.uint64)
        ps.lookup(signs, 4, True)
        ps.update_gradients(signs, np.ones((39, 4), np.float32), 4)
        expected = {int(s): ps.get_entry(int(s))[1] for s in signs[:5]}

    infer_holder = EmbeddingHolder(1000, 2)
    loaded = IncrementalUpdateLoader(infer_holder, str(inc_dir)).scan_once()
    assert loaded >= 39
    for s, vec in expected.items():
        np.testing.assert_array_equal(infer_holder.get_entry(s)[1], vec)


def test_dataflow_backpressure_retries():
    """A full forward buffer must stall the data-loader (with backoff),
    not drop batches (reference ForwardBufferFull contract). Verified
    against a synthetic worker that reports fullness twice."""
    receiver = DataflowReceiver()
    try:
        from persia_tpu.rpc import RpcError
        from persia_tpu.service.dataflow import DataflowClient

        class FullThenOkWorker:
            def __init__(self):
                self.calls = 0

            def put_batch(self, feats):
                self.calls += 1
                if self.calls < 3:
                    raise RpcError("x ForwardBufferFull y")
                return ("w", 7)

        w = FullThenOkWorker()
        client = DataflowClient(w, [receiver.addr])
        b = next(iter(batches(32, 32, seed=1)))
        client.send(b)
        assert w.calls == 3
        got = receiver.get(timeout=10)
        assert got.remote_ref == ("w", 7)
    finally:
        receiver.close()


def test_ps_infer_boot_with_initial_checkpoint(tmp_path):
    """Infer-mode PS boots with --initial-checkpoint loaded
    (reference: bin/persia-embedding-parameter-server.rs:108-116)."""
    import subprocess
    import sys as _sys
    import time as _time

    from persia_tpu.ps.store import EmbeddingHolder
    from persia_tpu.service.ps_service import PsClient
    from persia_tpu.utils import wait_addr_file

    # build a checkpoint file
    h = EmbeddingHolder(1000, 2)
    h.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
    h.register_optimizer({"type": "sgd", "lr": 0.1})
    signs = np.arange(1, 20, dtype=np.uint64)
    expected = h.lookup(signs, 4, True)
    ckpt = tmp_path / "initial.psd"
    h.dump_file(str(ckpt))

    import os as _os

    addr_file = str(tmp_path / "ps.addr")
    proc = subprocess.Popen(
        [_sys.executable, "-m", "persia_tpu.service.ps_service",
         "--port", "0", "--addr-file", addr_file,
         "--initial-checkpoint", str(ckpt)],
        env={**_os.environ,
             "PYTHONPATH": str(Path(__file__).resolve().parent.parent)},
    )
    try:
        ps = PsClient(wait_addr_file(addr_file, 60, proc))
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline:
            try:
                if len(ps) == 19:
                    break
            except Exception:
                pass
            _time.sleep(0.2)
        assert len(ps) == 19
        # eval lookups serve checkpointed values without an optimizer
        out = ps.lookup(signs, 4, False)
        np.testing.assert_array_equal(out, expected)
        ps.shutdown()
    finally:
        if proc.poll() is None:
            proc.kill()


def test_full_four_role_deployment_via_launcher_scripts():
    """The DEPLOY.md topology end to end with real role entry scripts:
    ServiceCtx cluster + nn_worker.py trainer subprocess +
    data_loader.py subprocess, all over the coordinator. Runs once, no
    retry: the startup race this used to absorb was the coordinator's
    find-free-port TOCTOU, fixed at the source (addr-file handoff)."""
    _run_four_role_deployment()


def _run_four_role_deployment():
    import os
    import subprocess
    import sys as _sys

    repo = str(Path(__file__).resolve().parent.parent)
    example = os.path.join(repo, "examples", "adult_income")
    with ServiceCtx(_schema(), n_workers=1, n_ps=1) as svc:
        env = {
            **os.environ,
            "PYTHONPATH": repo,
            "PERSIA_COORDINATOR_ADDR": svc.coordinator_addr,
            "JAX_PLATFORMS": "cpu",
            "RANK": "0", "WORLD_SIZE": "1", "REPLICA_INDEX": "0",
            "REPLICA_SIZE": "1",
        }
        trainer = subprocess.Popen(
            [_sys.executable, "-m", "persia_tpu.launcher", "nn-worker",
             os.path.join(example, "nn_worker.py")], env=env)
        loader = subprocess.Popen(
            [_sys.executable, "-m", "persia_tpu.launcher", "data-loader",
             os.path.join(example, "data_loader.py"),
             "--samples", "1536", "--batch-size", "256"], env=env)
        try:
            assert loader.wait(timeout=300) == 0
            assert trainer.wait(timeout=300) == 0
        finally:
            for p in (trainer, loader):
                if p.poll() is None:
                    p.kill()
