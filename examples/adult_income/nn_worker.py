"""nn-worker (trainer) role entry
(reference: examples/src/adult-income/train.py run under the launcher).

Registers a dataflow receiver with the coordinator, streams batches from
remote data-loaders, trains the DNN through remote embedding workers:

    PERSIA_COORDINATOR_ADDR=... RANK=0 WORLD_SIZE=1 \
        python -m persia_tpu.launcher nn-worker examples/adult_income/nn_worker.py
"""

import argparse
import os
import sys

try:  # prefer the installed package (pip install -e .)
    import persia_tpu  # noqa: F401
except ImportError:  # bare checkout fallback
    sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])
sys.path.insert(0, __file__.rsplit("/nn_worker.py", 1)[0])

import optax

from persia_tpu.config import EmbeddingSchema, uniform_slots
from persia_tpu.ctx import TrainCtx
from persia_tpu.data.dataloader import DataLoader, StreamingDataset
from persia_tpu.embedding import EmbeddingConfig
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.env import get_coordinator_addr, get_rank
from persia_tpu.logger import get_default_logger
from persia_tpu.models import DNN
from persia_tpu.service.coordinator import (
    ROLE_TRAINER,
    ROLE_WORKER,
    CoordinatorClient,
)
from persia_tpu.service.dataflow import DataflowReceiver
from persia_tpu.service.worker_service import RemoteEmbeddingWorker

from data_generator import NUM_SLOTS

logger = get_default_logger("nn_worker")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--num-workers", type=int, default=1)
    # env fallbacks mirror the reference's e2e compose contract
    # (REPRODUCIBLE=1 + EMBEDDING_STALENESS=1 -> deterministic runs);
    # empty/unset values fall back rather than crashing at startup
    try:
        staleness_default = int(os.environ.get("EMBEDDING_STALENESS") or 8)
    except ValueError:
        staleness_default = 8
    p.add_argument("--embedding-staleness", type=int,
                   default=staleness_default)
    p.add_argument("--reproducible", action="store_true",
                   default=os.environ.get("REPRODUCIBLE") == "1")
    args = p.parse_args()

    rank = get_rank()
    coord = CoordinatorClient(get_coordinator_addr())
    worker = RemoteEmbeddingWorker(
        coord.wait_members(ROLE_WORKER, args.num_workers, timeout=300))
    # the stream ends only after EVERY data-loader replica sends EOS
    receiver = DataflowReceiver(
        num_senders=int(os.environ.get("PERSIA_NUM_DATALOADERS") or 1))
    coord.register(ROLE_TRAINER, rank, receiver.addr)

    schema = EmbeddingSchema(
        slots_config=uniform_slots(
            [f"slot_{s}" for s in range(NUM_SLOTS)], dim=8))
    ctx = TrainCtx(
        model=DNN(),
        dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=1e-2),
        schema=schema,
        worker=worker,
        embedding_config=EmbeddingConfig(emb_initialization=(-0.05, 0.05)),
    )
    loader = DataLoader(StreamingDataset(receiver),
                        embedding_staleness=args.embedding_staleness,
                        reproducible=args.reproducible)
    steps = 0
    with ctx:
        for batch in loader:
            loss, _ = ctx.train_step(batch)
            if steps % 50 == 0:
                logger.info("step %d loss %.4f", steps, float(loss))
            steps += 1
    logger.info("stream ended after %d steps", steps)
    receiver.close()


if __name__ == "__main__":
    main()
