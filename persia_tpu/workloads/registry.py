"""Scenario registry: production-shaped workloads as first-class bench
drivers.

A :class:`Scenario` bundles everything a test or example needs to run
one zoo workload end to end on the existing stack — the embedding
schema (dims, pooling modes), the dense tower, the deterministic batch
generator, the loss, and the AUC floor
``tests/test_workloads.py::test_scenario_trains_through_hybrid_stack``
holds it to. Tests, the trainer service and the examples resolve
through :func:`get_scenario`, so all train the ONE shared workload
definition.

Scenario knobs: ``PERSIA_WORKLOAD_ALPHA`` (zipf skew) and
``PERSIA_WORKLOAD_SEED`` (base seed) set the defaults; ``get_scenario``
arguments override.
"""

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np

from persia_tpu.config import EmbeddingSchema, SlotConfig, uniform_slots
from persia_tpu.workloads import generator as gen


@dataclass(frozen=True)
class Scenario:
    """One runnable zoo workload (schema + model + stream + gates)."""

    name: str
    description: str
    schema: EmbeddingSchema
    model_fn: Callable[[], object]       # () -> flax module
    batches: Callable[..., Iterator]     # (num_samples, batch_size,
    #                                       seed=, requires_grad=) -> iter
    num_dense: int
    tasks: Tuple[str, ...] = ("ctr",)
    loss_fn: Optional[Callable] = None   # None -> ctx default (bce)
    # convergence smoke: held-out AUC floor (min over tasks) after the
    # smoke row budget; deliberately loose — it catches "not learning",
    # not "state of the art"
    auc_gate: float = 0.55
    # ragged (worker-pooled / raw) feature names, () when the wire
    # carries single-id features only (the byte-identical-wire pin arm)
    ragged_features: Tuple[str, ...] = ()
    # default per-step batch rows for the e2e bench (smoke shrinks it)
    bench_batch_size: int = 1024
    seed: int = 0

    def model(self):
        return self.model_fn()


_FACTORIES: Dict[str, Callable[..., Scenario]] = {}


def register_scenario(name: str):
    def deco(fn):
        _FACTORIES[name] = fn
        return fn
    return deco


def scenario_names() -> Tuple[str, ...]:
    return tuple(sorted(_FACTORIES))


def get_scenario(name: str, smoke: bool = False,
                 alpha: Optional[float] = None,
                 seed: Optional[int] = None, **kw) -> Scenario:
    """Resolve a scenario by name. ``smoke`` shrinks vocabs/batches to
    the CI row budget; ``alpha``/``seed`` default to the
    ``PERSIA_WORKLOAD_*`` knobs."""
    from persia_tpu import knobs

    if name not in _FACTORIES:
        raise KeyError(
            f"unknown scenario {name!r}; registered: "
            f"{', '.join(scenario_names())}")
    if alpha is None:
        alpha = float(knobs.get("PERSIA_WORKLOAD_ALPHA"))
    if seed is None:
        seed = int(knobs.get("PERSIA_WORKLOAD_SEED"))
    return _FACTORIES[name](smoke=smoke, alpha=alpha, seed=seed, **kw)


def _bind_seed(fn, default_seed):
    """Bind a generator (with its spec pre-applied via partial) to the
    scenario's default seed; callers may still override (eval streams
    pass seed+1000 and stay disjoint draws of the same task)."""
    def batches(num_samples, batch_size, seed=default_seed,
                requires_grad=True):
        return fn(num_samples, batch_size, seed=seed,
                  requires_grad=requires_grad)
    return batches


@register_scenario("dlrm")
def _dlrm(smoke: bool = False, alpha: float = 1.05, seed: int = 0,
          scale: Optional[float] = None) -> Scenario:
    """Criteo-schema DLRM: 26 zipf categorical tables with a realistic
    log-spread vocab/dim mix + 13 dense floats, mixed-dim interaction
    tower. The wire carries single-id features ONLY — this is the
    byte-identical-wire pin arm of the e2e gate, and the scenario whose
    traffic validates the hotness planner."""
    if scale is None:
        scale = 0.02 if smoke else 0.2
    spec = gen.CriteoSpec.build(scale=scale, alpha=alpha)
    slots = {
        name: SlotConfig(name=name, dim=spec.dims[t])
        for t, name in enumerate(gen.CRITEO_SLOT_NAMES)
    }
    schema = EmbeddingSchema(slots_config=slots)

    def model_fn():
        from persia_tpu.workloads.models import ZooDLRM

        return ZooDLRM(proj_dim=16)

    batches = _bind_seed(
        functools.partial(gen.dlrm_batches, spec=spec), seed)

    return Scenario(
        name="dlrm",
        description=("Criteo-schema DLRM: 26 zipf tables (mixed "
                     "vocab/dim), 13 dense, pairwise interaction"),
        schema=schema, model_fn=model_fn, batches=batches,
        num_dense=spec.num_dense, auc_gate=0.60,
        bench_batch_size=2048 if not smoke else 256, seed=seed)


@register_scenario("seqrec")
def _seqrec(smoke: bool = False, alpha: float = 1.05,
            seed: int = 0) -> Scenario:
    """Session recommendation over WORKER-pooled ragged history: a
    mean-pooled recent-items slot + a last-N-pooled clicks slot sharing
    the target's item sign space, label planted in history homogeneity."""
    spec = gen.SeqRecSpec(
        item_vocab=2_000 if smoke else 20_000,
        t_hist=12 if smoke else 20,
        alpha=alpha)
    dim = spec.dim
    slots = {
        **uniform_slots(list(gen.SEQ_PROFILE_SLOTS), dim=dim),
        gen.SEQ_HISTORY_SLOT: SlotConfig(
            name=gen.SEQ_HISTORY_SLOT, dim=dim, pooling="mean"),
        gen.SEQ_CLICKS_SLOT: SlotConfig(
            name=gen.SEQ_CLICKS_SLOT, dim=dim,
            pooling=f"last{spec.last_n}"),
        gen.SEQ_TARGET_SLOT: SlotConfig(
            name=gen.SEQ_TARGET_SLOT, dim=dim),
    }
    schema = EmbeddingSchema(slots_config=slots)

    def model_fn():
        from persia_tpu.workloads.models import PooledSessionNet

        return PooledSessionNet()

    batches = _bind_seed(
        functools.partial(gen.seqrec_batches, spec=spec), seed)

    return Scenario(
        name="seqrec",
        description=("session/sequence features: ragged histories "
                     "pooled mean + last-N on the worker tier"),
        schema=schema, model_fn=model_fn, batches=batches,
        num_dense=spec.num_dense, auc_gate=0.60,
        ragged_features=(gen.SEQ_HISTORY_SLOT, gen.SEQ_CLICKS_SLOT),
        bench_batch_size=512 if not smoke else 128, seed=seed)


@register_scenario("multitask")
def _multitask(smoke: bool = False, alpha: float = 1.05,
               seed: int = 0) -> Scenario:
    """Two objectives (click, convert) over one shared set of embedding
    tables; labels ride as one (batch, 2) array through the unchanged
    single-Label train path."""
    spec = gen.MultiTaskSpec(
        user_vocab=2_000 if smoke else 20_000,
        item_vocab=5_000 if smoke else 50_000,
        alpha=alpha)
    dim = spec.dim
    slots = {
        "user": SlotConfig(name="user", dim=dim),
        "item": SlotConfig(name="item", dim=dim),
        "ctx_0": SlotConfig(name="ctx_0", dim=8),
        "ctx_1": SlotConfig(name="ctx_1", dim=8),
    }
    schema = EmbeddingSchema(slots_config=slots)

    def model_fn():
        from persia_tpu.workloads.models import MultiTaskDNN

        return MultiTaskDNN(num_tasks=2)

    batches = _bind_seed(
        functools.partial(gen.multitask_batches, spec=spec), seed)

    from persia_tpu.workloads.models import multitask_bce

    return Scenario(
        name="multitask",
        description=("multi-task head (click + convert) sharing "
                     "embedding tables across two objectives"),
        schema=schema, model_fn=model_fn, batches=batches,
        num_dense=spec.num_dense, tasks=gen.MT_TASKS,
        loss_fn=multitask_bce, auc_gate=0.55,
        bench_batch_size=1024 if not smoke else 256, seed=seed)


# --- shared evaluation helper -------------------------------------------

def evaluate_auc(ctx, scenario: Scenario, num_samples: int = 4096,
                 batch_size: int = 512,
                 seed_offset: int = 1000) -> Dict[str, float]:
    """Held-out per-task AUC through the ctx's eval path. The eval
    stream uses ``scenario.seed + seed_offset`` — a disjoint draw from
    the SAME hidden task (the generators' determinism contract)."""
    from persia_tpu.ctx import eval_ctx
    from persia_tpu.utils import roc_auc

    preds, labels = [], []
    with eval_ctx(ctx) as ectx:
        for batch in scenario.batches(num_samples, batch_size,
                                      seed=scenario.seed + seed_offset,
                                      requires_grad=False):
            pred, lab = ectx.forward(batch)
            preds.append(np.asarray(pred))
            labels.append(np.asarray(lab[0]))
    pred = np.concatenate(preds)
    pred = pred.reshape(pred.shape[0], -1)
    label = np.concatenate(labels).reshape(pred.shape[0], -1)
    return {
        task: float(roc_auc(label[:, t], pred[:, t]))
        for t, task in enumerate(scenario.tasks)
    }
