"""Flagship service-mode e2e: the sharded-PS, data-parallel-dense shape end to end.

ServiceCtx cluster (2 embedding workers + 2 C++ `persia-embedding-ps`
binaries) + two Criteo data-loader replicas streaming learnable batches
over the dataflow + an 8-device CPU-mesh DDP trainer in this process —
the full distributed topology the reference runs on a GPU pod
(`/root/reference/k8s/resources/example.yaml` roles), asserted to
*learn* (AUC on held-out draws of the same hidden-weight task). Point
the same wiring at real TPU hardware and it is the production config-3
job.
"""

import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import optax

REPO = pathlib.Path(__file__).resolve().parent.parent
EX = REPO / "examples" / "criteo"
sys.path.insert(0, str(EX))

from criteo_data import SLOT_NAMES, learnable_batches  # noqa: E402

from persia_tpu.config import EmbeddingSchema, uniform_slots  # noqa: E402
from persia_tpu.ctx import TrainCtx, eval_ctx  # noqa: E402
from persia_tpu.data.dataloader import (  # noqa: E402
    DataLoader,
    StreamingDataset,
)
from persia_tpu.embedding import EmbeddingConfig  # noqa: E402
from persia_tpu.embedding.optim import Adagrad  # noqa: E402
from persia_tpu.models import DLRM  # noqa: E402
from persia_tpu.parallel.mesh import make_mesh  # noqa: E402
from persia_tpu.service.coordinator import ROLE_TRAINER  # noqa: E402
from persia_tpu.service.dataflow import DataflowReceiver  # noqa: E402
from persia_tpu.service.helper import ServiceCtx  # noqa: E402
from persia_tpu.utils import roc_auc  # noqa: E402

DIM = 16
VOCAB = 500            # per-slot; small so ids repeat and embeddings train
N_LOADERS = 2
SAMPLES = 49152        # total across loader replicas
BS = 256               # divisible by the 8-device data axis


def _schema():
    return EmbeddingSchema(slots_config=uniform_slots(SLOT_NAMES, dim=DIM))


def test_flagship_criteo_service_mesh():
    """Runs once, no retry: the startup race this test used to absorb
    was the coordinator's find-free-port TOCTOU, fixed at the source
    (ServiceCtx now hands the port off via an addr-file)."""
    _run_flagship()


def _run_flagship():
    with ServiceCtx(_schema(), n_workers=2, n_ps=2, native_ps=True,
                    ps_capacity=500_000, ps_num_shards=4) as svc:
        mesh = make_mesh((8, 1))
        ctx = TrainCtx(
            model=DLRM(embedding_dim=DIM),
            dense_optimizer=optax.adagrad(0.1),
            embedding_optimizer=Adagrad(lr=0.3),
            schema=_schema(),
            worker=svc.remote_worker(),
            embedding_config=EmbeddingConfig(emb_initialization=(-0.01, 0.01)),
            mesh=mesh,
        )
        receiver = DataflowReceiver(num_senders=N_LOADERS)
        svc.coordinator_client().register(ROLE_TRAINER, 0, receiver.addr)
        base_env = {
            **os.environ,
            "PYTHONPATH": str(REPO),
            "PERSIA_COORDINATOR_ADDR": svc.coordinator_addr,
            "JAX_PLATFORMS": "cpu",
            "PERSIA_NUM_WORKERS": "2",
            "WORLD_SIZE": "1",
        }
        loaders = [
            subprocess.Popen(
                [sys.executable, str(EX / "send_data.py"), "--learnable",
                 "--samples", str(SAMPLES),
                 "--batch-size", str(BS), "--vocab", str(VOCAB)],
                env={**base_env, "REPLICA_INDEX": str(i),
                     "REPLICA_SIZE": str(N_LOADERS)},
            )
            for i in range(N_LOADERS)
        ]
        import threading

        def _watch_loaders():
            """A loader that dies without EOS would otherwise hang the
            stream (and this test) forever: count it as EOS so the
            trainer loop ends and the exit-code asserts report it."""
            pending = set(range(len(loaders)))
            while pending:
                for i in sorted(pending):
                    if loaders[i].poll() is not None:
                        pending.discard(i)
                        if loaders[i].returncode != 0:
                            receiver.abort_sender(sender_id=i)
                time.sleep(0.5)

        threading.Thread(target=_watch_loaders, daemon=True).start()
        try:
            trained = 0
            steps = 0
            t0 = time.perf_counter()
            with ctx:
                loader = DataLoader(StreamingDataset(receiver),
                                    num_workers=2,
                                    embedding_staleness=8,
                                    forward_buffer_size=8)
                for batch in loader:
                    loss, _ = ctx.train_step(batch)
                    trained += BS
                    steps += 1
                elapsed = time.perf_counter() - t0
                assert np.isfinite(float(loss))
                assert trained >= SAMPLES  # every replica's shard arrived

                preds, labels = [], []
                with eval_ctx(ctx) as ectx:
                    for b in learnable_batches(4096, BS, seed=99,
                                               vocab_per_slot=VOCAB,
                                               requires_grad=False):
                        p, ls = ectx.forward(b)
                        preds.append(np.asarray(p))
                        labels.append(np.asarray(ls[0]))
            auc = roc_auc(np.concatenate(labels).ravel(),
                          np.concatenate(preds).ravel())
            print(f"flagship: {steps} steps, {trained} samples in "
                  f"{elapsed:.1f}s = {trained / elapsed:,.0f} samples/s, "
                  f"held-out auc {auc:.4f}")
            assert auc > 0.60, f"AUC {auc} — distributed path not learning"
            for p in loaders:
                assert p.wait(timeout=60) == 0
        finally:
            for p in loaders:
                if p.poll() is None:
                    p.kill()
            receiver.close()
