"""User-facing contexts: the core API (reference: persia/ctx.py).

- :class:`BaseCtx` — enter/exit + ``current_ctx()`` registry
  (ctx.py:202-271)
- :class:`DataCtx` — data-loader role, ``send_data`` into the dataflow
  (ctx.py:274-342)
- :class:`EmbeddingCtx` — embedding lookup, feature preparation, dump/load
  (ctx.py:345-652)
- :class:`TrainCtx` — adds the dense optimizer and the full hybrid train
  step (ctx.py:655-1064). In JAX the reference's forward/backward pair
  collapses into one compiled step whose outputs include the embedding
  gradients; ``train_step`` then routes them to the parameter servers —
  the sparse update stays asynchronous with respect to the next batch's
  lookup when driven through the DataLoader pipeline.
- :class:`InferCtx` — direct lookup + eval-mode forward (ctx.py:1077-1133)

The embedding tier is reached through an :class:`EmbeddingWorker`; in
local (in-process) mode its PS clients are EmbeddingHolders, in service
mode they are RPC clients — the ctx code is identical.
"""

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from persia_tpu import tracing
from persia_tpu.config import EmbeddingSchema, GlobalConfig
from persia_tpu.data.batch import PersiaBatch
from persia_tpu.embedding import EmbeddingConfig, get_default_embedding_config
from persia_tpu.logger import get_default_logger
from persia_tpu.worker.middleware import RawEmbedding, SumEmbedding
from persia_tpu.worker.worker import EmbeddingWorker

_logger = get_default_logger(__name__)

_ctx_lock = threading.Lock()
_ctx_stack: List["BaseCtx"] = []


def current_ctx() -> Optional["BaseCtx"]:
    return _ctx_stack[-1] if _ctx_stack else None


class BaseCtx:
    """Contexts nest (an eval_ctx may open inside a TrainCtx with-block,
    mirroring the reference's usage in examples/src/adult-income/train.py);
    ``current_ctx`` returns the innermost."""

    def __enter__(self):
        with _ctx_lock:
            _ctx_stack.append(self)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        with _ctx_lock:
            if self in _ctx_stack:
                _ctx_stack.remove(self)
        return False


class DataCtx(BaseCtx):
    """Data-loader role: push batches toward the embedding workers and
    trainers (reference ctx.py:274-342).

    In service mode ``dataflow`` is a persia_tpu.service dataflow client;
    in local mode batches go straight to a local EmbeddingWorker.
    """

    def __init__(self, dataflow=None):
        self.dataflow = dataflow
        self._next_batch_id = 0

    def send_data(self, batch: PersiaBatch):
        if self.dataflow is None:
            raise RuntimeError("DataCtx requires a dataflow client")
        if batch.batch_id is None:
            batch.batch_id = self._next_batch_id
        self._next_batch_id = batch.batch_id + 1
        self.dataflow.send(batch)


class EmbeddingCtx(BaseCtx):
    def __init__(
        self,
        model=None,
        schema: Optional[EmbeddingSchema] = None,
        worker: Optional[EmbeddingWorker] = None,
        embedding_config: Optional[EmbeddingConfig] = None,
        global_config: Optional[GlobalConfig] = None,
    ):
        self.model = model
        self.schema = schema if schema is not None else (
            worker.schema if worker is not None else None
        )
        self.worker = worker
        self.embedding_config = embedding_config or get_default_embedding_config()
        self.global_config = global_config or GlobalConfig()
        self._configured_servers = False

    def __enter__(self):
        super().__enter__()
        if self.worker is not None and not self._configured_servers:
            self.configure_embedding_parameter_servers()
        return self

    def configure_embedding_parameter_servers(self):
        """Broadcast hyperparameters to every PS
        (reference: lib.rs:307-318 -> mod.rs:429-451)."""
        ec = self.embedding_config
        init = self.schema.initialization if self.schema else None
        if init is not None and init.method.value != "bounded_uniform":
            method, params = init.method.value, init.to_params()
        else:
            lower, upper = ec.emb_initialization
            method, params = "bounded_uniform", {"lower": lower, "upper": upper}
        self.worker.configure_parameter_servers(
            method, params, ec.admit_probability, ec.weight_bound,
            enable_weight_bound=True,
        )
        self._configured_servers = True

    def register_optimizer(self, optimizer):
        """Called by embedding Optimizer.apply()."""
        self.worker.register_optimizer(optimizer.config)

    # --- feature preparation -------------------------------------------

    def prepare_features(
        self, batch: PersiaBatch, lookup: Dict[str, Any]
    ) -> Tuple[List[jnp.ndarray], List[Any], List[jnp.ndarray]]:
        """Worker lookup results -> device-ready model inputs
        (reference: _prepare_feature, ctx.py:75-199)."""
        non_id = [jnp.asarray(f.data) for f in batch.non_id_type_features]
        labels = [jnp.asarray(l.data) for l in batch.labels]
        emb_inputs: List[Any] = []
        for f in batch.id_type_features:
            r = lookup[f.name]
            if isinstance(r, SumEmbedding):
                emb_inputs.append(jnp.asarray(r.embeddings))
            elif isinstance(r, RawEmbedding):
                emb_inputs.append(
                    (jnp.asarray(r.embeddings), jnp.asarray(r.index))
                )
            else:
                raise TypeError(f"unexpected lookup result {type(r)}")
        return non_id, emb_inputs, labels

    def forward(self, batch: PersiaBatch):
        """Eval/infer forward: direct lookup + model apply
        (reference: forward_directly path, ctx.py:433-469)."""
        lookup = self.worker.lookup_direct(batch.id_type_features,
                                           training=False)
        return self.forward_prepared(batch, lookup)

    def forward_prepared(self, batch: PersiaBatch, lookup: Dict[str, Any]):
        """Forward from an ALREADY-performed lookup — the serving tier's
        entry point: its hot-row cache resolves the embeddings itself
        (serving.py `_lookup_cached`) and only needs the feature
        preparation + jitted eval apply from the ctx."""
        non_id, emb_inputs, labels = self.prepare_features(batch, lookup)
        pred = self._apply_model(non_id, emb_inputs)
        return pred, labels

    def _apply_model(self, non_id, emb_inputs):
        raise NotImplementedError

    # --- checkpointing ---------------------------------------------------

    def dump_checkpoint(self, dst_dir: str, with_dense: bool = True):
        from persia_tpu import checkpoint as ckpt

        ckpt.dump_checkpoint(self, dst_dir, with_dense=with_dense)

    def load_checkpoint(self, src_dir: str, with_dense: bool = True):
        from persia_tpu import checkpoint as ckpt

        ckpt.load_checkpoint(self, src_dir, with_dense=with_dense)


class TrainCtx(EmbeddingCtx):
    """Training context: hybrid sync-dense / async-sparse step.

    Args mirror the reference TrainCtx (ctx.py:655-852) where they still
    make sense on TPU; DDP options collapse into an optional mesh.
    """

    def __init__(
        self,
        model,
        dense_optimizer: optax.GradientTransformation,
        embedding_optimizer,
        schema: EmbeddingSchema,
        worker: EmbeddingWorker,
        embedding_config: Optional[EmbeddingConfig] = None,
        global_config: Optional[GlobalConfig] = None,
        mesh=None,
        loss_fn=None,
        grad_update_interval: int = 1,
        seed: int = 0,
        grad_reduce_dtype: Optional[str] = None,
        device_cache_capacity: int = 0,
        device_cache_admission: Optional[str] = None,
        profiler=None,
        resume_from: Optional[str] = None,
    ):
        super().__init__(model=model, schema=schema, worker=worker,
                         embedding_config=embedding_config,
                         global_config=global_config)
        from persia_tpu.parallel.train import bce_loss

        self.dense_optimizer = dense_optimizer
        self.embedding_optimizer = embedding_optimizer
        self.mesh = mesh
        self.loss_fn = loss_fn or bce_loss
        self.grad_update_interval = grad_update_interval
        self.seed = seed
        # "bf16" halves dense all-reduce bytes over ICI (the Bagua
        # low-precision-algorithm analogue); None = full f32 reduction
        self.grad_reduce_dtype = grad_reduce_dtype
        self.state = None
        self._train_step = None
        self._eval_step = None
        self._emb_shapes = None
        self._ddp = False
        # error-feedback residuals for grad_reduce_dtype="int8_ef"
        # (per-replica, data-axis-sharded; see parallel/train.py)
        self._ef_state = None
        # device-resident hot-row cache (TPU-first, beyond the reference:
        # hits never cross the host<->device wire; see
        # persia_tpu/parallel/cached_engine.py for the consistency model).
        # admission: None -> the PERSIA_TIER_ADMIT knob; "hotness"
        # selects the frequency-gated tier-ladder mapper
        self.device_cache_capacity = int(device_cache_capacity)
        if self.device_cache_capacity and not (
                hasattr(worker, "lookup_rows_with_state")
                and hasattr(worker, "set_rows")):
            raise TypeError(
                f"device_cache_capacity={self.device_cache_capacity} needs "
                "a worker with lookup_rows_with_state/set_rows (the cache "
                "engine moves rows WITH their optimizer state); "
                f"{type(worker).__name__} has neither RPC. Host the worker "
                "in the trainer's process: EmbeddingWorker(schema, "
                "[PsClient(a) for a in ps_addrs])")
        self.device_cache_admission = device_cache_admission
        self._cache_engine = None
        self._cached_step = None
        self._cache_multi_id = False
        # opt-in device profiler window (tracing.StepProfiler): a
        # jax.profiler trace capture keyed to a step range; the spans of
        # exactly those steps land in the same xplane as the TPU
        # timeline. Defaults from PERSIA_PROFILE_DIR/_START_STEP/
        # _NUM_STEPS.
        self.profiler = (profiler if profiler is not None
                         else tracing.profiler_from_env())
        self._step_count = 0
        # --- whole-job resume (persia_tpu/snapshot.py) -----------------
        # `resume_from` names one snapshot directory or a snapshot_dir
        # parent (newest complete wins). Resolution + verification
        # happen HERE so a torn/absent snapshot fails at construction,
        # not mid-__enter__; the sparse rollback runs on __enter__ and
        # the dense bytes install lazily once the TrainState exists.
        self.resume_manifest: Optional[dict] = None
        self.resume_cursor: Optional[dict] = None
        self._resume_snap: Optional[str] = None
        self._pending_dense: Optional[bytes] = None
        if resume_from:
            from persia_tpu import snapshot as _snapshot

            self._resume_snap, self.resume_manifest = (
                _snapshot.resolve_snapshot(resume_from))
            self.resume_cursor = _snapshot.load_cursor(self._resume_snap)

    def __enter__(self):
        super().__enter__()
        if self.embedding_optimizer is not None:
            self.embedding_optimizer.apply()
        if self._cache_engine is not None:
            self._cache_engine.ensure_open()  # re-entry after __exit__
        if self._resume_snap is not None:
            self._restore_from_snapshot()
        return self

    def _restore_from_snapshot(self):
        """Roll the job back to the resolved snapshot: PS stores wiped
        to the snapshot's consistent cut (post-snapshot updates are
        re-derived by replaying the deterministic batch stream from
        ``resume_cursor``), dense bytes staged for lazy install, step
        counter restored. Runs once; re-entering the ctx later must
        not re-wipe live training progress."""
        from persia_tpu import snapshot as _snapshot

        snap, self._resume_snap = self._resume_snap, None
        if self._cache_engine is not None:
            self._cache_engine.invalidate()  # cached rows predate restore
        self.worker.load(snap)
        self._pending_dense = _snapshot.dense_bytes(snap)
        self._step_count = int(self.resume_manifest.get("step", 0))

    def snapshot(self, snapshot_dir: str, cursor: Optional[dict] = None,
                 inc_dir: Optional[str] = None,
                 keep: Optional[int] = None) -> str:
        """Take one coordinated job snapshot (persia_tpu/snapshot.py):
        device cache flushed, backward pipeline drained, then sparse +
        dense + cursor captured as one manifest-stamped unit."""
        from persia_tpu import snapshot as _snapshot

        self.flush_device_cache()
        return _snapshot.snapshot_job(
            snapshot_dir, self.worker, state=self.state, cursor=cursor,
            inc_dir=inc_dir, step=self._step_count, keep=keep)

    def _wire_dtype(self):
        return (
            jnp.bfloat16
            if self.global_config.common.embedding_wire_dtype == "bf16"
            else jnp.float32
        )

    def _use_ddp_step(self, emb_indices, batch_size: int) -> bool:
        """Mesh present + every slot summed + batch divisible by the data
        axis -> the explicit shard_map DDP step with batch-major packed
        wire. Raw slots' shared distinct tensors cannot batch-shard, and
        a partial final batch cannot split evenly — both keep the
        auto-sharded path (shard_batch_pytree's replicate fallback)."""
        if self.mesh is None or any(i is not None for i in emb_indices):
            return False
        from persia_tpu.parallel.mesh import DATA_AXIS

        return batch_size % self.mesh.shape[DATA_AXIS] == 0

    def _ensure_compiled(self, non_id, emb_inputs) -> bool:
        """Builds the state and the step for this batch geometry where
        they are missing; True when it built a step."""
        from persia_tpu.parallel.train import (
            create_train_state,
            make_eval_step,
            make_packed_train_step,
            make_packed_train_step_ddp,
            split_embedding_inputs,
        )

        emb_values, emb_indices = split_embedding_inputs(emb_inputs)
        emb_shapes = tuple(tuple(v.shape) for v in emb_values)
        if self.state is None:
            self.state = create_train_state(
                self.model, self.dense_optimizer, jax.random.key(self.seed),
                non_id, emb_inputs,
            )
            self._eval_step = make_eval_step(self.model)
        if self._pending_dense is not None:
            # snapshot resume: install the dumped model + optimizer
            # leaves into the freshly built (template) TrainState
            from persia_tpu import checkpoint as _ckpt

            self.state = _ckpt.apply_dense_bytes(self.state,
                                                 self._pending_dense)
            self._pending_dense = None
        if self._train_step is None or emb_shapes != self._emb_shapes:
            # (re)build the packed step for this batch geometry; jit caches
            # by shape so alternating geometries stay cheap
            self._emb_shapes = emb_shapes
            reduce_dtype = {
                "bf16": jnp.bfloat16, "int8_ef": "int8_ef",
            }.get(self.grad_reduce_dtype)
            batch_size = emb_shapes[0][0] if emb_shapes else 0
            if self._use_ddp_step(emb_indices, batch_size):
                self._ddp = True
                self._slot_dims = [s[1] for s in emb_shapes]
                self._train_step = make_packed_train_step_ddp(
                    self.model, self.dense_optimizer, self._slot_dims,
                    self.mesh, loss_fn=self.loss_fn,
                    wire_dtype=self._wire_dtype(),
                    grad_reduce_dtype=reduce_dtype,
                )
                if reduce_dtype == "int8_ef" and self._ef_state is None:
                    from persia_tpu.parallel.train import init_ef_state

                    self._ef_state = init_ef_state(
                        self.state.params, self.mesh)
            else:
                self._ddp = False
                self._train_step = make_packed_train_step(
                    self.model, self.dense_optimizer, emb_shapes,
                    loss_fn=self.loss_fn, wire_dtype=self._wire_dtype(),
                )
            return True
        return False

    def _prep_train_inputs(self, batch: PersiaBatch,
                           lookup: Dict[str, Any]) -> tuple:
        """Lookup results -> train-step inputs, uploading the embedding
        values ONLY as the single packed wire blob.

        Unlike :meth:`prepare_features` (the eval path), the per-slot
        value matrices stay numpy: the jitted train step consumes the
        packed array, so per-slot device uploads would both double the
        pinned device memory and force a device->host round trip at
        pack time. Returns (non_id, emb_inputs_host, emb_shapes,
        flat_emb, emb_indices, labels). The packed layout is batch-major
        ``(batch, sum dims)`` for the DDP shard_map step (batch axis
        shards over the mesh), flat otherwise."""
        from persia_tpu.parallel.train import (
            pack_embedding_values,
            pack_embedding_values_batch_major,
        )

        with tracing.span("trainer/prep_inputs"):
            non_id = [jnp.asarray(f.data) for f in batch.non_id_type_features]
            labels = [jnp.asarray(l.data) for l in batch.labels]
            emb_np: List[np.ndarray] = []
            emb_indices: List[Any] = []
            emb_inputs: List[Any] = []  # host-side, for model init/shapes only
            for f in batch.id_type_features:
                r = lookup[f.name]
                if isinstance(r, SumEmbedding):
                    emb_np.append(r.embeddings)
                    emb_indices.append(None)
                    emb_inputs.append(r.embeddings)
                elif isinstance(r, RawEmbedding):
                    idx = jnp.asarray(r.index)
                    emb_np.append(r.embeddings)
                    emb_indices.append(idx)
                    emb_inputs.append((r.embeddings, idx))
                else:
                    raise TypeError(f"unexpected lookup result {type(r)}")
            emb_shapes = tuple(tuple(v.shape) for v in emb_np)
            if self._use_ddp_step(emb_indices, len(labels[0])):
                from persia_tpu.parallel.mesh import batch_sharding

                flat_emb = jax.device_put(
                    pack_embedding_values_batch_major(emb_np,
                                                      self._wire_dtype()),
                    batch_sharding(self.mesh),
                )
            else:
                flat_emb = jnp.asarray(
                    pack_embedding_values(emb_np, self._wire_dtype())
                )
            return (non_id, emb_inputs, emb_shapes, flat_emb, emb_indices,
                    labels)

    def stage_batch(self, batch: PersiaBatch, lookup: Dict[str, Any]):
        """Host->device staging for one looked-up batch, run by the
        forward engine's prefetch workers so the uploads overlap the
        previous batch's compute (the reference's postprocess_worker
        moves batches to the GPU off the training thread via pinned
        pools, forward.rs:572-638 + cuda/). Returns the staged tuple the
        next ``train_step`` consumes; None when staging does not apply
        (mesh placement happens on the training thread)."""
        if self.mesh is not None:
            return None
        return self._prep_train_inputs(batch, lookup)

    def train_step(self, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """One full hybrid step: lookup -> dense step -> sparse update.

        Accepts a raw :class:`PersiaBatch` (synchronous lookup + update)
        or a pipeline :class:`~persia_tpu.pipeline.LookedUpBatch` from a
        DataLoader, in which case the lookup already happened in a
        prefetch worker (with host->device staging done there too) and
        the gradient update is submitted to the async backward engine
        (bounded by the staleness semaphore).

        Embedding values/gradients cross the host<->device boundary as a
        single packed bf16 array in each direction (the TPU analogue of
        the reference's f16 wire, persia-common/src/lib.rs:85-113).
        Returns (loss, pred).

        Observability: each step runs under a ``trainer/train_step``
        span — joined to the batch's existing trace when it came through
        the pipeline (the prefetch worker's lookup opened the root), a
        fresh root otherwise — with one child span per phase the step
        runs (``trainer/lookup_direct``, ``trainer/prep_inputs``,
        ``trainer/place_batch``, ``trainer/dispatch``,
        ``trainer/grad_submit``; ``cache/*`` on the cached path), and
        drives the opt-in :class:`~persia_tpu.tracing.StepProfiler`
        window."""
        from persia_tpu.pipeline import LookedUpBatch

        self._step_count += 1
        if self.profiler is not None:
            self.profiler.on_step(self._step_count)
        tctx = batch.trace if isinstance(batch, LookedUpBatch) else None
        kw = {"ctx": tctx} if tctx is not None else {"root": True}
        with tracing.span("trainer/train_step", step=self._step_count,
                          **kw):
            return self._train_step_inner(batch)

    def _train_step_inner(self, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        from persia_tpu.parallel.train import unpack_embedding_grads
        from persia_tpu.pipeline import LookedUpBatch

        if self.device_cache_capacity and not (
                self._cache_engine is None and jax.process_count() > 1
                and self._negotiate_multihost_cache()):
            if isinstance(batch, LookedUpBatch):
                # DataLoader yields raw batches when the active ctx is
                # cached (dataloader.py), so a pre-looked-up batch here
                # means an engine was driven against this ctx by hand
                raise RuntimeError(
                    "device-cache ctx received a pre-looked-up batch; "
                    "the cache path does its own (cheaper) miss lookups "
                    "— feed raw PersiaBatch objects (DataLoader does "
                    "this automatically for cached ctxs)")
            return self._cached_train_step(batch)

        engine = None
        staged = None
        if isinstance(batch, LookedUpBatch):
            ref_id, lookup, engine = batch.ref_id, batch.lookup, batch.engine
            staged = batch.staged
            batch = batch.batch
        else:
            with tracing.span("trainer/lookup_direct"):
                ref_id, lookup = self.worker.lookup_direct_training(
                    batch.id_type_features
                )
        if staged is None:
            staged = self._prep_train_inputs(batch, lookup)
        non_id, emb_inputs, _emb_shapes, flat_emb, emb_indices, labels = staged
        compiled = self._ensure_compiled(non_id, emb_inputs)
        if self.mesh is not None:
            from persia_tpu.parallel.mesh import shard_batch_pytree

            placed = shard_batch_pytree(
                {"n": non_id, "i": emb_indices, "l": labels[0]}, self.mesh
            )
            non_id, emb_indices, label = placed["n"], placed["i"], placed["l"]
        else:
            label = labels[0]
        with tracing.span("trainer/dispatch", compiled=compiled):
            if self._ddp:
                if self._ef_state is not None:
                    (self.state, loss, flat_grads, pred,
                     self._ef_state) = self._train_step(
                        self.state, non_id, flat_emb, label, self._ef_state)
                else:
                    self.state, loss, flat_grads, pred = self._train_step(
                        self.state, non_id, flat_emb, label
                    )
            else:
                self.state, loss, flat_grads, pred = self._train_step(
                    self.state, non_id, flat_emb, emb_indices, label
                )
        names = [f.name for f in batch.id_type_features]
        slot_dims = self._slot_dims if self._ddp else None
        with tracing.span("trainer/grad_submit"):
            if engine is not None:
                # the device->host gradient fetch happens in a backward
                # worker thread, not here — on a slow host link a
                # synchronous fetch would serialize every step on the d2h
                # transfer
                engine.backward.submit_packed(
                    ref_id, flat_grads, self._emb_shapes, names,
                    slot_dims=slot_dims)
            else:
                if self._ddp:
                    from persia_tpu.parallel.train import (
                        unpack_embedding_grads_batch_major,
                    )

                    per_slot = unpack_embedding_grads_batch_major(
                        flat_grads, slot_dims)
                else:
                    per_slot = unpack_embedding_grads(flat_grads,
                                                      self._emb_shapes)
                self.worker.update_gradients(
                    ref_id, dict(zip(names, per_slot)))
        return loss, pred

    def _apply_model(self, non_id, emb_inputs):
        from persia_tpu.parallel.train import split_embedding_inputs

        self._ensure_compiled(non_id, emb_inputs)
        emb_values, emb_indices = split_embedding_inputs(emb_inputs)
        return self._eval_step(self.state, non_id, emb_values, emb_indices)

    # --- device-resident cache path --------------------------------------

    def _negotiate_multihost_cache(self) -> bool:
        """Multi-process mesh + device cache requested: decide between
        the historic hard error and a loud negotiate-down.

        ``PERSIA_MULTIHOST_CACHE=off`` (default) disables the cache and
        lets the run continue on the PS-only hybrid path — a pod job
        must not die on a cache knob. ``refuse`` preserves the hard
        error (we return False and :meth:`_ensure_cache` raises).
        Returns True when the cache was negotiated off."""
        from persia_tpu import knobs

        mode = str(knobs.get("PERSIA_MULTIHOST_CACHE")).lower()
        if mode == "refuse":
            return False
        if mode != "off":
            raise ValueError(
                f"PERSIA_MULTIHOST_CACHE={mode!r}: expected 'off' or "
                "'refuse'")
        _logger.warning(
            "device cache requested (capacity=%d) on a multi-process "
            "mesh (jax.process_count()=%d) — the cache's sign->slot "
            "mapper and miss/evict host transfers are single-controller "
            "state; NEGOTIATING DOWN: device cache DISABLED, continuing "
            "on the PS-only hybrid path. Set PERSIA_MULTIHOST_CACHE="
            "refuse to make this a hard error instead.",
            self.device_cache_capacity, jax.process_count())
        self.device_cache_capacity = 0
        return True

    def _ensure_cache(self, batch: PersiaBatch):
        """First-batch validation + lazy build of the cache engine and
        the fused cached step. The v2 envelope: uniform dim, SUMMED
        slots, non-shared Adagrad. Single-id slots take the pure-gather
        fast path; multi-id bags take the segment-sum step (with
        sqrt_scaling parity). A mesh is supported — the cache becomes
        one GSPMD row-sharded array (cached_train._row_sharding).
        Anything outside the envelope raises with the reason rather
        than silently degrading. True when it built them."""
        if self._cache_engine is not None:
            return False
        if jax.process_count() > 1:
            # Single-controller constraint: the engine's sign->slot map,
            # miss imports and eviction write-backs are host-side state
            # on THIS process, while a multi-process mesh shards the
            # cache arrays across hosts — remote rows would be
            # imported/flushed by a host that cannot address them, and
            # every process would run a divergent mapper. A multi-host
            # cache needs per-process row ownership (shard the mapper by
            # jax.process_index) before this can be lifted.
            raise NotImplementedError(
                "device cache is single-controller only: "
                f"jax.process_count()={jax.process_count()} — the "
                "sign->slot mapper and miss/evict host transfers live "
                "on one process; use the uncached hybrid path (or "
                "device mode) on multi-process meshes — or leave "
                "PERSIA_MULTIHOST_CACHE=off to negotiate the cache "
                "down instead of erroring")
        from persia_tpu.embedding.optim import Adagrad as ClientAdagrad

        opt = self.embedding_optimizer
        if not isinstance(opt, ClientAdagrad) or opt.vectorwise_shared:
            raise NotImplementedError(
                "device cache mirrors non-shared Adagrad on device; "
                f"got {type(opt).__name__}")
        from persia_tpu.data.batch import IDTypeFeatureWithSingleID

        # Mode dispatch is TYPE-based, not shape-based: the SingleID
        # class guarantees one id per sample on EVERY batch, so the
        # fast pure-gather path can never meet a later variable-length
        # batch. Base IDTypeFeature streams (even if the first batch
        # happens to look single-id) take the general bag path — a
        # first-batch shape probe would lock in the wrong step.
        multi_id = not all(
            isinstance(f, IDTypeFeatureWithSingleID)
            for f in batch.id_type_features)
        dims = set()
        for f in batch.id_type_features:
            slot = self.schema.get_slot(f.name)
            # both cached steps feed the model per-slot (B, D) pooled
            # values; a raw (non-summed) slot expects the padded
            # distinct + index representation and would be silently
            # sum-pooled — reject regardless of observed bag shape
            if not slot.embedding_summation:
                raise NotImplementedError(
                    "device cache needs summed (pooled) slots; "
                    f"{f.name} is a raw slot")
            if slot.pooling != "sum":
                # the fused cached step segment-SUMS bags on device;
                # running a mean/last-k slot through it would silently
                # change the pooling semantics
                raise NotImplementedError(
                    "device cache supports pooling='sum' slots only; "
                    f"{f.name} uses pooling={slot.pooling!r} (worker-"
                    "tier pooling) — use the uncached hybrid path")
            dims.add(slot.dim)
        if len(dims) != 1:
            raise NotImplementedError(
                f"device cache needs one uniform slot dim, got {dims}")
        dim = dims.pop()
        num_slots = len(batch.id_type_features)
        from persia_tpu.parallel.cached_engine import DeviceCacheEngine
        from persia_tpu.parallel.cached_train import (
            make_cached_bag_train_step,
            make_cached_train_step,
        )

        self._cache_engine = DeviceCacheEngine(
            self.worker, self.device_cache_capacity, num_slots, dim,
            acc_init=opt.initial_accumulator_value, mesh=self.mesh,
            sqrt_scaling=[
                self.schema.get_slot(f.name).sqrt_scaling
                for f in batch.id_type_features],
            admission=self.device_cache_admission)
        self._cache_multi_id = multi_id
        maker = make_cached_bag_train_step if multi_id \
            else make_cached_train_step
        self._cached_step = maker(
            self.model, self.dense_optimizer, num_slots, dim,
            lr=opt.lr, eps=opt.eps,
            g_square_momentum=opt.g_square_momentum,
            loss_fn=self.loss_fn,
            weight_bound=self.embedding_config.weight_bound,
            capacity=self.device_cache_capacity, mesh=self.mesh)
        if self.state is None:
            from persia_tpu.parallel.train import create_train_state

            batch_size = len(batch.labels[0].data)
            non_id = [jnp.asarray(f.data)
                      for f in batch.non_id_type_features]
            dummy_emb = [np.zeros((batch_size, dim), np.float32)
                         for _ in range(num_slots)]
            self.state = create_train_state(
                self.model, self.dense_optimizer,
                jax.random.key(self.seed), non_id, dummy_emb)
            from persia_tpu.parallel.train import make_eval_step

            self._eval_step = make_eval_step(self.model)
        if self._pending_dense is not None:
            from persia_tpu import checkpoint as _ckpt

            self.state = _ckpt.apply_dense_bytes(self.state,
                                                 self._pending_dense)
            self._pending_dense = None
        return True

    def _cached_train_step(self, batch: PersiaBatch):
        compiled = self._ensure_cache(batch)
        eng = self._cache_engine
        non_id = [jnp.asarray(f.data) for f in batch.non_id_type_features]
        label = jnp.asarray(batch.labels[0].data)
        if self._cache_multi_id:
            (flat_slot_idx, seg, scale, cold_idx, cold_vals, cold_acc,
             evicted, evicted_mask, inverse,
             unique_slots) = eng.prepare_bags(batch.id_type_features)
            positions = (flat_slot_idx, seg, scale)
        else:
            (slot_idx, cold_idx, cold_vals, cold_acc, evicted,
             evicted_mask, inverse,
             unique_slots) = eng.prepare(batch.id_type_features)
            positions = (slot_idx,)
        with tracing.span("trainer/dispatch", compiled=compiled):
            (self.state, eng.cache_vals, eng.cache_acc, loss, pred,
             ev_vals, ev_acc) = self._cached_step(
                self.state, eng.cache_vals, eng.cache_acc, non_id,
                *map(jnp.asarray, positions + (
                    cold_idx, cold_vals, cold_acc, inverse, unique_slots)),
                label)
        eng.finish(evicted, evicted_mask, ev_vals, ev_acc)
        return loss, pred

    def flush_device_cache(self) -> int:
        """Write every cached row back to the PS (eval/checkpoint entry
        points call this; the cache stays valid for more training)."""
        if self._cache_engine is None:
            return 0
        return self._cache_engine.flush_all()

    def __exit__(self, exc_type, exc_val, exc_tb):
        # leaving the ctx must leave the PS authoritative (a later
        # InferCtx / dump / second TrainCtx reads it) and must not leak
        # the flush thread; super().__exit__ must run even when the
        # flush raises, or the dead ctx stays on the _ctx_stack and
        # current_ctx() keeps returning it
        if self.profiler is not None:
            self.profiler.close()  # stop an open device-trace capture
        try:
            if self._cache_engine is not None:
                try:
                    if exc_type is None:
                        self.flush_device_cache()
                finally:
                    self._cache_engine.close()
        finally:
            result = super().__exit__(exc_type, exc_val, exc_tb)
        return result

    def dump_checkpoint(self, dst_dir: str, with_dense: bool = True):
        self.flush_device_cache()
        super().dump_checkpoint(dst_dir, with_dense=with_dense)

    def load_checkpoint(self, src_dir: str, with_dense: bool = True):
        # invalidate (NOT flush) first: cached rows predate the restore;
        # flushing them — or serving further hits from them — would
        # clobber the loaded values
        if self._cache_engine is not None:
            self._cache_engine.invalidate()
        super().load_checkpoint(src_dir, with_dense=with_dense)


class InferCtx(EmbeddingCtx):
    """Inference: fixed worker addresses, eval-mode lookups
    (reference ctx.py:1077-1133).

    The eval step is built once and jit-caches per input geometry, so
    the number of XLA compiles equals the number of distinct batch-row
    shapes the server feeds it. ``eval_batch_rows_seen`` records those
    shapes — the serving tier's shape-bucketing exists exactly to keep
    this set equal to its bucket ladder instead of one entry per
    coalesced request count (see serving.py)."""

    def __init__(self, model, state, schema, worker, **kw):
        super().__init__(model=model, schema=schema, worker=worker, **kw)
        self.state = state
        self._eval_step = None
        self.eval_batch_rows_seen: set = set()

    def _apply_model(self, non_id, emb_inputs):
        from persia_tpu.parallel.train import (
            make_eval_step,
            split_embedding_inputs,
        )

        if self._eval_step is None:
            self._eval_step = make_eval_step(self.model)
        emb_values, emb_indices = split_embedding_inputs(emb_inputs)
        rows = None
        if non_id:
            rows = int(non_id[0].shape[0])
        else:
            # embedding-only model: summed slots are (batch, dim); raw
            # slots carry batch rows in their (batch, sfs) index tensor
            for v, idx in zip(emb_values, emb_indices):
                rows = int(v.shape[0] if idx is None else idx.shape[0])
                break
        if rows is not None:
            if rows not in self.eval_batch_rows_seen:
                # replace-on-write, not .add(): a concurrent stats
                # reader iterating the old set must never see it mutate
                # mid-iteration (serving's stats RPC runs on another
                # thread); a lost concurrent insert re-adds on the next
                # call with the same shape
                self.eval_batch_rows_seen = (
                    self.eval_batch_rows_seen | {rows})
        return self._eval_step(self.state, non_id, emb_values, emb_indices)


class _EvalCtx(EmbeddingCtx):
    def __init__(self, parent: TrainCtx):
        super().__init__(model=parent.model, schema=parent.schema,
                         worker=parent.worker,
                         embedding_config=parent.embedding_config)
        self._parent = parent
        self._configured_servers = True  # already configured by parent
        # cached rows train on device; make the PS authoritative before
        # eval lookups read it
        parent.flush_device_cache()

    def _apply_model(self, non_id, emb_inputs):
        return self._parent._apply_model(non_id, emb_inputs)


def eval_ctx(train_ctx: Optional[TrainCtx] = None) -> _EvalCtx:
    """Evaluation context over a trained TrainCtx (reference ctx.py:1072).

    Must be entered after exiting (or outside) the TrainCtx with-block.
    """
    ctx = train_ctx or current_ctx()
    if not isinstance(ctx, TrainCtx):
        raise RuntimeError("eval_ctx requires a TrainCtx")
    return _EvalCtx(ctx)
