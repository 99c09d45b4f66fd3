"""Long-history sequence recommendation — the long-context flagship.

A DIN/SASRec-style CTR model: summed profile slots + ONE raw
variable-length user-history slot flowing through
:class:`persia_tpu.models.SequenceTower` (self-attention over the
history, masked mean pooling), trained through the full hybrid stack
(embedding worker -> C++/numpy PS -> jitted JAX step). The synthetic
task plants the signal IN the history (the label depends on whether
recent history items share the target item's hidden affinity), so a
model that ignores the sequence tower cannot beat AUC 0.5.

Long-context scale-out: ``--mesh 1,4 --context-parallel ulysses
[--attn-impl pallas]`` shards the HISTORY AXIS over the mesh's model
axis (ring attention or Ulysses all-to-all; optionally the Pallas
flash kernel per shard) — the same command shape works from t=64 on a
CPU mesh to tens-of-thousands-long histories on a TPU pod where the
O(T^2) score matrix could never materialize.

    python examples/seq_rec/train.py --steps 300
    python examples/seq_rec/train.py --mesh 1,4 --context-parallel ulysses

Reference parity note: the CUDA reference has no sequence/long-context
support; this example is persia_tpu-only surface (SURVEY.md §5 row
"Long-context/SP").
"""

import argparse
import os
import sys

import numpy as np

try:  # prefer the installed package (pip install -e .)
    import persia_tpu  # noqa: F401
except ImportError:  # bare checkout fallback
    sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

if os.environ.get("JAX_PLATFORMS") == "cpu":
    from persia_tpu.utils import force_cpu_platform

    force_cpu_platform(8)

import optax

from persia_tpu.config import EmbeddingSchema, SlotConfig, uniform_slots
from persia_tpu.ctx import TrainCtx, eval_ctx
from persia_tpu.embedding import EmbeddingConfig
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.logger import get_default_logger
from persia_tpu.models import SequenceTower
from persia_tpu.ps.native import make_holder
from persia_tpu.utils import enable_compile_cache, roc_auc, setup_seed
from persia_tpu.worker.worker import EmbeddingWorker
from persia_tpu.workloads.generator import (
    SEQ_CLICKS_SLOT,
    SEQ_HISTORY_SLOT,
    SEQ_PROFILE_SLOTS,
    SEQ_TARGET_SLOT,
    SeqRecSpec,
    seqrec_batches,
)

logger = get_default_logger("seq_rec")

DIM = 16


def make_batches(args, num_samples, batch_size, seed=0,
                 requires_grad=True):
    """The workload zoo's shared session stream (the label hides in
    history-cluster homogeneity; see
    persia_tpu/workloads/generator.py:seqrec_batches). This example
    reads the SAME stream through a different schema lens than
    the registry's `seqrec` scenario: recent_items stays a RAW
    slot here so the attention tower sees the full sequence, while the
    clicks slot exercises worker-tier last-N pooling."""
    spec = SeqRecSpec(item_vocab=args.vocab, t_hist=args.t_hist)
    return seqrec_batches(num_samples, batch_size, seed=seed, spec=spec,
                          requires_grad=requires_grad)


def build_ctx(args, mesh=None):
    setup_seed(args.seed)
    slots = uniform_slots(
        [*SEQ_PROFILE_SLOTS, SEQ_TARGET_SLOT], dim=DIM)
    # attention wants the raw sequence; the clicks slot rides the
    # worker-tier recency pooling (one (bs, dim) vector on the wire)
    slots[SEQ_HISTORY_SLOT] = SlotConfig(
        name=SEQ_HISTORY_SLOT, dim=DIM, embedding_summation=False,
        sample_fixed_size=args.t_hist)
    slots[SEQ_CLICKS_SLOT] = SlotConfig(
        name=SEQ_CLICKS_SLOT, dim=DIM, pooling="last4")
    schema = EmbeddingSchema(slots_config=slots)
    holders = [make_holder(2_000_000, 8) for _ in range(args.n_ps)]
    worker = EmbeddingWorker(schema, holders)
    model = SequenceTower(
        num_heads=args.heads, mesh=mesh,
        context_parallel=args.context_parallel,
        attn_impl=args.attn_impl)
    return TrainCtx(
        model=model,
        dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=1e-2),
        schema=schema,
        worker=worker,
        embedding_config=EmbeddingConfig(emb_initialization=(-0.05, 0.05)),
        seed=args.seed,
    )


def evaluate(ctx, args, num_samples=4096):
    preds, labels = [], []
    with eval_ctx(ctx) as ectx:
        for batch in make_batches(args, num_samples, args.batch_size,
                                  seed=args.seed + 1000,
                                  requires_grad=False):
            pred, lab = ectx.forward(batch)
            preds.append(np.asarray(pred).reshape(-1))
            labels.append(np.asarray(lab[0]).reshape(-1))
    return roc_auc(np.concatenate(labels), np.concatenate(preds))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--t-hist", type=int, default=64,
                   help="max history length (the sequence axis)")
    p.add_argument("--vocab", type=int, default=50_000,
                   help="item sign space of the shared zoo generator")
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--n-ps", type=int, default=2)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--mesh", default=None,
                   help="data,model e.g. 1,4 — model axis shards the "
                        "history length (context parallelism)")
    p.add_argument("--context-parallel", choices=["ring", "ulysses"],
                   default="ring")
    p.add_argument("--attn-impl", choices=["xla", "pallas"], default="xla")
    args = p.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh:
        import jax

        from persia_tpu.parallel.mesh import make_mesh

        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, devices=jax.devices()[:shape[0] * shape[1]])
        if args.t_hist % shape[1]:
            p.error("--t-hist must divide by the model-axis size")

    ctx = build_ctx(args, mesh=mesh)
    with ctx:
        n = 0
        for step, batch in enumerate(make_batches(
                args, args.steps * args.batch_size, args.batch_size,
                seed=args.seed)):
            loss, _ = ctx.train_step(batch)
            n += 1
            if step % 50 == 0:
                logger.info(f"step {step}: loss {float(loss):.4f}")
        auc = evaluate(ctx, args)
        logger.info(f"trained {n} steps, test AUC {auc:.4f}")
        print(f"AUC: {auc:.4f}")
        return 0 if auc > 0.62 else 1


if __name__ == "__main__":
    sys.exit(main())
