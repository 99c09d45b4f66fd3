"""Criteo DLRM training — the reference's workload.

Maps onto its configurations:
  1/2. single worker + in-process or remote PS:  default flags
  3.   multi-chip data-parallel dense:           --mesh data,model (e.g. 8,1)
  4.   alternate towers:                          --model dcnv2|deepfm
  5.   100B-scale synthetic:                      --synthetic + big --vocab

Run with the real dataset (Kaggle DAC train.txt / Terabyte day_*):

    python examples/criteo/train.py --train path/train.txt \
        --test path/test.txt [--mesh 8,1]

or without it:  python examples/criteo/train.py --synthetic
"""

import argparse
import os
import sys

import numpy as np

try:  # prefer the installed package (pip install -e .)
    import persia_tpu  # noqa: F401
except ImportError:  # bare checkout fallback
    sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if os.environ.get("JAX_PLATFORMS") == "cpu":
    from persia_tpu.utils import force_cpu_platform

    force_cpu_platform(8)

import optax

from persia_tpu.config import EmbeddingSchema, uniform_slots
from persia_tpu.ctx import TrainCtx
from persia_tpu.data.dataloader import DataLoader, IterableDataset
from persia_tpu.embedding import EmbeddingConfig
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.logger import get_default_logger
from persia_tpu.models import DCNv2, DeepFM, DLRM
from persia_tpu.workloads.models import ZooDLRM
from persia_tpu.ps.native import make_holder
from persia_tpu.utils import enable_compile_cache, roc_auc, setup_seed
from persia_tpu.worker.worker import EmbeddingWorker

from criteo_data import (  # unique module name: examples share sys.path
    SLOT_NAMES,
    criteo_batches,
    synthetic_batches,
)

logger = get_default_logger("criteo")

# "zoo-dlrm" is the workload zoo's mixed-dim tower (per-field projection
# before the interaction): the one to pick when the schema YAML ladders
# dims by table cardinality instead of using one uniform width
ZOO = {"dlrm": DLRM, "dcnv2": DCNv2, "deepfm": DeepFM,
       "zoo-dlrm": ZooDLRM}


def load_schema(args) -> EmbeddingSchema:
    """ONE schema source: the config YAML the service roles also load
    (diverging code- and file-defined schemas would mismatch embedding
    widths across roles); --dim falls back only when the file is absent."""
    if os.path.exists(args.embedding_config):
        return EmbeddingSchema.load(args.embedding_config)
    return EmbeddingSchema(
        slots_config=uniform_slots(SLOT_NAMES, dim=args.dim),
        feature_index_prefix_bit=12,
    )


def build_ctx(args, schema: EmbeddingSchema, worker=None):
    setup_seed(args.seed)
    if worker is None:
        holders = [
            make_holder(args.ps_capacity, args.ps_shards)
            for _ in range(args.n_ps)
        ]
        worker = EmbeddingWorker(schema, holders)
    mesh = None
    if args.mesh:
        from persia_tpu.parallel.mesh import make_mesh

        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape)
    dim = schema.get_slot(SLOT_NAMES[0]).dim
    model_kw = {"embedding_dim": dim} if args.model == "dlrm" else (
        {"proj_dim": dim} if args.model == "zoo-dlrm" else {})
    return TrainCtx(
        model=ZOO[args.model](**model_kw),
        dense_optimizer=optax.adagrad(args.lr),
        embedding_optimizer=Adagrad(lr=args.sparse_lr),
        schema=schema,
        worker=worker,
        embedding_config=EmbeddingConfig(emb_initialization=(-0.01, 0.01)),
        mesh=mesh,
        grad_reduce_dtype=args.grad_reduce_dtype,
        seed=args.seed,
    )


def batches_for(args, requires_grad=True, test=False):
    if args.synthetic or not args.train:
        n = args.test_samples if test else args.samples
        return synthetic_batches(
            n, args.batch_size, seed=99 if test else args.seed,
            vocab_per_slot=args.vocab, requires_grad=requires_grad)
    # no separate test file: evaluate on a slice of the train file
    path = (args.test or args.train) if test else args.train
    return criteo_batches(path, args.batch_size,
                          max_samples=args.test_samples if test
                          else args.samples,
                          requires_grad=requires_grad)


def main_remote(args, schema: EmbeddingSchema) -> None:
    """Service-mode trainer (the k8s job's nnWorker entry): discover the
    embedding-worker fleet through the coordinator, register a dataflow
    receiver, and stream batches pushed by the data-loader role — the
    same wiring as examples/adult_income/nn_worker.py."""
    from persia_tpu.data.dataloader import StreamingDataset
    from persia_tpu.env import get_coordinator_addr, get_rank
    from persia_tpu.service.coordinator import (
        ROLE_TRAINER,
        ROLE_WORKER,
        CoordinatorClient,
    )
    from persia_tpu.service.dataflow import DataflowReceiver
    from persia_tpu.service.worker_service import RemoteEmbeddingWorker

    coord = CoordinatorClient(get_coordinator_addr())
    worker = RemoteEmbeddingWorker(
        coord.wait_members(ROLE_WORKER, args.num_remote_workers,
                           timeout=300))
    # the stream ends only after EVERY data-loader replica sends EOS
    n_loaders = int(os.environ.get("PERSIA_NUM_DATALOADERS") or 1)
    receiver = DataflowReceiver(num_senders=n_loaders)
    coord.register(ROLE_TRAINER, get_rank(), receiver.addr)
    ctx = build_ctx(args, schema, worker=worker)
    loader = DataLoader(StreamingDataset(receiver),
                        num_workers=args.num_workers,
                        embedding_staleness=args.staleness,
                        forward_buffer_size=args.staleness)
    steps = 0
    with ctx:
        for batch in loader:
            loss, _ = ctx.train_step(batch)
            if steps % args.log_every == 0:
                logger.info("step %d loss %.5f", steps, float(loss))
            steps += 1
    logger.info("stream ended after %d steps", steps)
    receiver.close()


def main(args) -> float:
    schema = load_schema(args)
    if os.environ.get("PERSIA_COORDINATOR_ADDR") and not args.local:
        main_remote(args, schema)
        return float("nan")  # service mode: AUC computed offline
    ctx = build_ctx(args, schema)
    with ctx:
        loader = DataLoader(
            IterableDataset(batches_for(args)),
            num_workers=args.num_workers,
            embedding_staleness=args.staleness,
            forward_buffer_size=args.staleness,
        )
        for i, batch in enumerate(loader):
            loss, _ = ctx.train_step(batch)
            if i % args.log_every == 0:
                logger.info("step %d loss %.5f", i, float(loss))
        # evaluation
        preds, labels = [], []
        from persia_tpu.ctx import eval_ctx

        with eval_ctx(ctx) as ectx:
            for batch in batches_for(args, requires_grad=False, test=True):
                pred, label = ectx.forward(batch)
                preds.append(np.asarray(pred))
                labels.append(np.asarray(label[0]))
    auc = roc_auc(np.concatenate(labels), np.concatenate(preds))
    logger.info("test auc %.6f", auc)
    return auc


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--train", default=None, help="Criteo tsv(.gz)")
    p.add_argument("--test", default=None)
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--local", action="store_true",
                   help="force in-process PS even when a coordinator "
                        "address is in the environment")
    p.add_argument("--embedding-config",
                   default=os.path.join(os.path.dirname(
                       os.path.abspath(__file__)),
                       "config", "embedding_config.yml"),
                   help="schema YAML (shared with the service roles)")
    p.add_argument("--num-remote-workers", type=int,
                   default=int(os.environ.get("PERSIA_NUM_WORKERS", 1)),
                   help="embedding-worker replicas to wait for "
                        "(service mode)")
    p.add_argument("--model", choices=sorted(ZOO), default="dlrm")
    p.add_argument("--dim", type=int, default=16,
                   help="fallback dim when --embedding-config is absent")
    p.add_argument("--batch-size", type=int, default=4096)
    p.add_argument("--samples", type=int, default=512_000)
    p.add_argument("--test-samples", type=int, default=65_536)
    p.add_argument("--vocab", type=int, default=1 << 20,
                   help="synthetic sign space per slot")
    p.add_argument("--n-ps", type=int, default=2)
    p.add_argument("--ps-capacity", type=int, default=1_000_000_000)
    p.add_argument("--ps-shards", type=int, default=16)
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--sparse-lr", type=float, default=0.02)
    p.add_argument("--staleness", type=int, default=8)
    p.add_argument("--num-workers", type=int, default=4)
    p.add_argument("--mesh", default=os.environ.get("PERSIA_MESH"),
                   help="e.g. 8,1 for 8-way DP (env PERSIA_MESH)")
    p.add_argument("--grad-reduce-dtype", default=None,
                   choices=[None, "bf16"], help="bf16 halves DP all-reduce")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=50)
    args = p.parse_args()
    enable_compile_cache()
    auc = main(args)
    print(f"AUC: {auc}")
