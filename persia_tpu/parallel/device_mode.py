"""Fully device-resident training: dense tower + sharded HBM embeddings.

This mode composes :class:`DeviceEmbeddingCollection` (tables sharded over
the mesh's ``model`` axis) with any dense tower from the model zoo into a
single jitted train step — dense DP allreduce and embedding-shard
collectives are both XLA-inserted over ICI. It is the TPU-first
alternative to the CPU parameter-server path and the configuration the
multi-chip dry run exercises.

The step updates only the rows a batch touches: it gathers them,
differentiates with respect to them (not the tables), sums the gradients
of equal ids, and runs the caller's optimizer over those rows and the
same rows of its table-shaped state, written back in place
(``device_embedding.update_touched_rows``); the tower's leaves go through
the same ``optimizer.update`` call whole. That equals the whole-table
step only for an optimizer that leaves a row without gradient alone
(Adagrad, plain SGD). ``make_device_mode_trainer`` tries the optimizer it
is given on a tiny table (``device_embedding.rows_suffice``) and, where
the rows do not suffice (Adam, momentum, weight decay) or the model has
no ``table_rows``, keeps the dense step: ``jax.value_and_grad`` over the
whole tree and ``optimizer.update`` over every table. The gauges
``device_mode_row_update_tables`` and ``device_mode_dense_update_tables``
say which one a build took.

A tower may describe its own build (optional; the DLRM-family towers do
not): a method ``step_tags() -> {name: int or sequence}``. What it
returns becomes tags of the span ``trainer/build_device_step`` as given,
and gauges ``device_mode_<name>`` (an int as it is, a sequence by its
length, a word that names a choice as a gauge of 1 labelled
``choice=<word>``). The hybrid sequence tower gives ``tower_layers`` (its pattern),
``experts_held``, ``experts_routed``, ``expert_matrices`` (2 for
square-relu experts, 3 for silu-gated ones), ``mtp_depth``,
``residual_streams``, ``sinkhorn_iters`` (0 at one stream),
``attention_residuals_kept`` (the attention layers whose kernel's
``out`` and ``lse`` its ``nn.remat`` policy keeps),
``hyper_fused_sublayers`` (the sublayers whose hyper-connection runs
``ops/hyper_connection``'s kernels: all of them over several residual
streams, 0 over one), ``kda_layers`` (its delta-rule layers, with their
``kda_heads`` and the ``kda_chunk`` their recurrence runs in, and
``kda_fused_layers``, those of them whose recurrence runs
``ops/kda_scan``'s kernels and whose output the ``nn.remat`` policy
keeps: all of them; all four 0 without such a layer),
``attention_positions`` (1 where latent
attention rotates a part of its queries and keys or selected attention
rotates them whole, 0 where attention
carries no position and leaves it to the other layers),
``selected_layers`` (the layers whose attention is over the keys a
learned indexer selects, with ``select_topk``, the keys a query,
``index_heads``, and ``index_fused_layers``, those of them whose
indexer's tile admits the block of ``ops/sparse_select``'s kernels, so
that over a history of whole tiles and whole key blocks their index
scores and the scores' pullback run there: all or none; all four 0
without such a layer), ``expert_scoring``
(the router's rule, ``sigmoid`` or ``softmax``: a word) and, where its
pattern has attention, ``key_width`` and ``value_width``.

The step keeps its own account (:class:`DeviceStep`, which
``make_device_mode_trainer`` returns in the jitted function's place): a
``trainer/dispatch`` span a call with the device buffers that go in and
come out, gauges for the build and the first call
(``device_mode_init_seconds``, ``device_mode_first_call_seconds``,
``device_mode_first_call_compile_seconds``), a counter of the calls
after the first that compiled (``device_mode_step_recompiles_total``),
the operator's profiler window (``PERSIA_PROFILE_DIR``) and, on
request, the scope of every instruction of its compiled program
(:meth:`DeviceStep.scopes`).
"""

import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax.core import meta
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from persia_tpu import metrics, tracing
from persia_tpu.logger import get_default_logger
from persia_tpu.parallel.device_embedding import (
    DeviceEmbeddingCollection,
    distinct_rows,
    rows_suffice,
    table_rows,
    update_touched_rows,
)
from persia_tpu.parallel.mesh import replicated
from persia_tpu.parallel.train import bce_loss

_logger = get_default_logger(__name__)

# the collection's place in DeviceModeModel's parameter tree
TABLES = "DeviceEmbeddingCollection_0"


class DeviceModeModel(nn.Module):
    """Dense tower + device embedding tables as one module.

    ``slot_specs``: sequence of (name, vocab_size, dim) for the hashed
    HBM tables; ``tower``: a model-zoo module instance, which may have
    ``step_tags()`` (this module's docstring says what for). ``rows``,
    if given, mirrors the tables' place in the parameters
    (:meth:`table_rows`) and holds their values already gathered.
    """

    slot_specs: Sequence[Any]
    tower: nn.Module
    pooling: str = "sum"

    @nn.compact
    def __call__(self, non_id_tensors, id_tensors: Dict[str, jnp.ndarray],
                 train: bool = False, rows=None):
        embs = DeviceEmbeddingCollection(
            slot_specs=self.slot_specs, pooling=self.pooling, name=TABLES,
        )(id_tensors, None if rows is None else rows[TABLES])
        with jax.named_scope("tower"):
            return self.tower(non_id_tensors, embs, train=train)

    @nn.nowrap
    def table_rows(self, id_tensors):
        """The row of its table each id reads, as a tree that mirrors the
        tables' leaves of this module's parameters."""
        return {TABLES: table_rows(self.slot_specs, id_tensors)}


def _abstract(x):
    """Shape, dtype and sharding of one argument, and nothing of its
    value (a donated array's are still there after the call)."""
    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                sharding=getattr(x, "sharding", None),
                                weak_type=aval.weak_type)


def _changed(a, b) -> Optional[str]:
    """``float32[16,13] -> float32[32,13]`` where two abstract arguments
    differ in shape, dtype or (beyond equivalence on their devices)
    sharding; None where they do not."""
    def brief(x, placed):
        at = f"@{x.sharding}" if placed else ""
        return f"{x.dtype}[{','.join(map(str, x.shape))}]{at}"

    moved = (a.sharding is not None and b.sharding is not None
             and a.shape == b.shape
             and not a.sharding.is_equivalent_to(b.sharding, len(a.shape)))
    if (a.shape, a.dtype, a.weak_type) == (b.shape, b.dtype, b.weak_type) \
            and not moved:
        return None
    return f"{brief(a, moved)} -> {brief(b, moved)}"


class DeviceStep:
    """The jitted device-mode step and its account. Called as the jitted
    function is, ``step(params, opt_state, non_id, ids, label) ->
    (params, opt_state, loss)``; ``lower`` and every other attribute are
    the jitted function's own.

    - Each call runs under ``tracing.span("trainer/dispatch")``. While
      the span records (``PERSIA_TRACING`` or a live profiler session)
      it is tagged ``args`` and ``results``, the device buffers that go
      into and come out of the call (leaves, counted at the first call),
      and ``compiled``. With both switches off the call pays one frame,
      the shared null span and three reads of the compile watch.
    - The first call sets ``device_mode_first_call_seconds`` (trace,
      lower, compile or cache load, dispatch; the device's run is not
      waited for) and ``device_mode_first_call_compile_seconds``. A
      later call during which the process compiled bumps
      ``device_mode_step_recompiles_total`` and logs the arguments that
      differ from the first call's.
    - ``profiler`` (``tracing.profiler_from_env()`` in
      ``make_device_mode_trainer``) gets ``on_step(i)`` before call
      ``i``; :meth:`close` stops a window that is still open.
    """

    def __init__(self, jitted, mesh: Mesh,
                 profiler: Optional[tracing.StepProfiler] = None):
        self._jitted = jitted
        self._mesh = mesh
        self._watch = tracing.compile_watch()
        self._profiler = profiler
        if profiler is not None and profiler.scopes is None:
            profiler.scopes = self.scopes
        self._calls = 0
        self._avals = None      # the first call's arguments, abstract
        self._buffers = None    # leaves (in, out) of the first call
        self._scopes = None
        self._recompiles = metrics.default_registry().counter(
            "device_mode_step_recompiles_total",
            help_text="calls of the device-mode step after the first "
                      "during which the process compiled")

    def __getattr__(self, name):
        # only what this class lacks comes here: the jitted function's
        if name == "_jitted":
            raise AttributeError(name)
        return getattr(self._jitted, name)

    def __call__(self, params, opt_state, non_id, ids, label):
        args = (params, opt_state, non_id, ids, label)
        if self._profiler is not None:
            self._profiler.on_step(self._calls)
        first = self._avals is None
        if first:
            self._avals = jax.tree_util.tree_map(_abstract, args)
            t0 = time.perf_counter()
        watch = self._watch
        compiles, seconds = watch.compiles, watch.seconds
        with tracing.span("trainer/dispatch") as sp:
            out = self._jitted(*args)
            compiled = watch.compiles != compiles
            if first:
                self._buffers = (len(jax.tree_util.tree_leaves(args)),
                                 len(jax.tree_util.tree_leaves(out)))
            elif compiled:
                self._recompiled(args)
            if sp.ctx is not None:
                sp.tag(args=self._buffers[0], results=self._buffers[1],
                       compiled=compiled)
        if first:
            reg = metrics.default_registry()
            reg.gauge("device_mode_first_call_seconds").set(
                time.perf_counter() - t0)
            reg.gauge("device_mode_first_call_compile_seconds").set(
                watch.seconds - seconds)
        self._calls += 1
        return out

    def _recompiled(self, args):
        self._recompiles.inc()
        was = jax.tree_util.tree_flatten_with_path(self._avals)[0]
        now = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(_abstract, args))
        if len(was) != len(now):
            differ = [f"{len(was)} -> {len(now)} leaves"]
        else:
            changes = ((path, _changed(a, b))
                       for (path, a), b in zip(was, now))
            differ = [f"{jax.tree_util.keystr(path)}: {change}"
                      for path, change in changes if change]
        if len(differ) > 8:
            differ[8:] = [f"and {len(differ) - 8} more"]
        _logger.warning(
            "device-mode step compiled again at call %d: %s", self._calls,
            "; ".join(differ) or "no argument differs from the first "
            "call's in shape, dtype or sharding (another thread's "
            "compilation, or a weak type)")

    def scopes(self) -> Dict[str, Tuple[str, bool]]:
        """``tracing.scope_table`` of this step's compiled program:
        ``{instruction name: (scope path, backward)}``, for
        ``tracing.device_time_by_scope`` to group a trace's device events
        by. Lowered from the first call's abstract arguments (shapes,
        dtypes, shardings; no array is held) and compiled, which with a
        persistent compile cache is a load; kept after the first request.
        Never on the step's path, and not free: it loads a second copy of
        the executable (hundreds of MB of code for a large tower), so ask
        once the state is freed, or catch the failure."""
        if self._scopes is None:
            if self._avals is None:
                raise RuntimeError("the step has not been called yet")
            with self._mesh:
                compiled = self._jitted.lower(*self._avals).compile()
            self._scopes = tracing.scope_table(compiled.as_text())
        return self._scopes

    def close(self):
        """Stops the profiler's window if it is still open."""
        if self._profiler is not None:
            self._profiler.close()


def make_device_mode_trainer(
    model: nn.Module,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    sample_non_id,
    sample_ids: Dict[str, jnp.ndarray],
    loss_fn: Callable = bce_loss,
    seed: int = 0,
) -> Tuple[Any, Any, Callable]:
    """Initialize sharded params + opt state and build the jitted step.

    Returns (params, opt_state, step) where ``step`` is a
    :class:`DeviceStep` over the jitted
    ``step(params, opt_state, non_id, ids, label) ->
    (params, opt_state, loss)``. Parameter shardings come from the
    modules' ``with_partitioning`` metadata; everything else replicates.
    ``opt_state`` is ``optimizer.init(params)`` whichever step is built.
    The step's operations carry the scopes ``tables_gather``, ``tower``
    and ``optimizer`` in their metadata, for a trace to group them by;
    the touched-rows step's table work inside ``optimizer`` carries
    ``row_update`` besides. The gauge ``device_mode_init_seconds`` is
    the time from entry to the ``trainer/build_device_step`` span:
    ``model.init``, the parameters' placement, ``optimizer.init``.
    """
    t0 = time.perf_counter()
    with mesh:
        variables = model.init(jax.random.key(seed), sample_non_id,
                               sample_ids, train=False)
    specs = nn.get_partition_spec(variables)["params"]
    params = meta.unbox(variables["params"])

    def shard_of(spec):
        if isinstance(spec, P):
            return NamedSharding(mesh, spec)
        return replicated(mesh)

    shardings = jax.tree_util.tree_map(
        shard_of, specs,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )
    params = jax.tree_util.tree_map(jax.device_put, params, shardings)
    # a leaf the optimizer made from nothing (Adam's step count) lies on
    # no mesh yet, while every output of the step does: placed now, the
    # second call finds the first call's compilation
    opt_state = jax.tree_util.tree_map(
        lambda x: x if isinstance(x, jax.core.Tracer) or x.committed
        else jax.device_put(x, replicated(mesh)), optimizer.init(params))
    metrics.default_registry().gauge("device_mode_init_seconds").set(
        time.perf_counter() - t0)

    with tracing.span("trainer/build_device_step") as built:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        paths = [path for path, _ in flat]
        tables, in_rows = [], None
        if hasattr(model, "table_rows"):
            tables, in_rows = jax.tree_util.tree_flatten_with_path(
                jax.eval_shape(model.table_rows, sample_ids))
        by_row = bool(tables) and rows_suffice(optimizer)
        counts = {"row_update_tables": len(tables) if by_row else 0,
                  "dense_update_tables": 0 if by_row else len(tables)}
        built.tag(**counts)
        # what the tower says of its own build, where it has step_tags()
        # (optional: the module's docstring): tags as given, and as
        # gauges the count of what a tag lists
        tower = getattr(model, "tower", None)
        described = tower.step_tags() if hasattr(tower, "step_tags") else {}
        built.tag(**described)
        # a word that names a choice (every str but the pattern, which
        # is a sequence of layers) is a gauge of 1 labelled with it
        chosen = {name: v for name, v in described.items()
                  if isinstance(v, str) and name != "tower_layers"}
        counts.update({name: v if isinstance(v, int) else len(v)
                       for name, v in described.items()
                       if name not in chosen})
        for name, n in counts.items():
            metrics.default_registry().gauge(f"device_mode_{name}").set(n)
        for name, word in chosen.items():
            metrics.default_registry().gauge(
                f"device_mode_{name}", labels={"choice": word}).set(1)

    def account(step):
        return DeviceStep(jax.jit(step, donate_argnums=(0, 1)), mesh,
                          tracing.profiler_from_env())

    if not by_row:
        def step(params, opt_state, non_id, ids, label):
            def compute_loss(params):
                pred = model.apply({"params": params}, non_id, ids,
                                   train=True)
                return loss_fn(pred, label)

            loss, grads = jax.value_and_grad(compute_loss)(params)
            with jax.named_scope("optimizer"):
                updates, opt_state2 = optimizer.update(grads, opt_state,
                                                       params)
                params2 = optax.apply_updates(params, updates)
            return params2, opt_state2, loss

        return params, opt_state, account(step)

    # where in the flat parameters the tables are, and the other leaves
    at_tables = [paths.index(path) for path, _ in tables]
    at_dense = [i for i in range(len(paths)) if i not in at_tables]

    def step(params, opt_state, non_id, ids, label):
        leaves = treedef.flatten_up_to(params)
        index = in_rows.flatten_up_to(model.table_rows(ids))
        with jax.named_scope("tables_gather"):
            rows = [jnp.take(leaves[i], at, axis=0)
                    for i, at in zip(at_tables, index)]

        def tree_of(dense, at_the_tables):
            full = list(leaves)     # the tables ride along where unnamed
            for i, leaf in zip(at_dense + at_tables,
                               list(dense) + list(at_the_tables)):
                full[i] = leaf
            return treedef.unflatten(full)

        def compute_loss(dense, rows):
            # the model reads the given rows, not the tables
            pred = model.apply({"params": tree_of(dense, [])}, non_id, ids,
                               train=True, rows=in_rows.unflatten(rows))
            return loss_fn(pred, label)

        loss, (grads, row_grads) = jax.value_and_grad(
            compute_loss, argnums=(0, 1))([leaves[i] for i in at_dense], rows)
        with jax.named_scope("optimizer"):
            with jax.named_scope("row_update"):
                touched, summed = _distinct_and_summed(
                    index, row_grads,
                    [leaves[i].shape[0] for i in at_tables])
            # every shard of a table sees all of the batch's rows and
            # writes its own: left to itself on a (2, 2) mesh the
            # partitioner scatters by data shard and all-reduces whole
            # tables
            touched, summed = jax.lax.with_sharding_constraint(
                (touched, summed), replicated(mesh))
            params2, opt_state2 = update_touched_rows(
                optimizer, params, opt_state, tree_of(grads, summed),
                tree_of([False] * len(at_dense), touched))
        return params2, opt_state2, loss

    return params, opt_state, account(step)


def _distinct_and_summed(index, row_grads, row_counts):
    """Each table's distinct rows (``distinct_rows``' ``touched``) and one
    summed gradient for each: the per-occurrence gradients of equal ids
    add up, as the dense scatter-add adds them, before an optimizer
    squares anything. Tables that read equally many ids share one batched
    sort."""
    touched, summed = [None] * len(index), [None] * len(index)
    by_size = {}
    for t, at in enumerate(index):
        by_size.setdefault(at.size, []).append(t)
    for size, group in by_size.items():
        distinct, slot = distinct_rows(
            jnp.stack([index[t].reshape(size) for t in group]),
            jnp.asarray([row_counts[t] for t in group], jnp.int32))
        for k, t in enumerate(group):
            touched[t] = distinct[k]
            summed[t] = jax.ops.segment_sum(
                row_grads[t].reshape(size, -1), slot[k], num_segments=size)
    return touched, summed


def criteo_like_specs(num_slots: int = 26, vocab: int = 1 << 16,
                      dim: int = 16):
    return [(f"slot_{i}", vocab, dim) for i in range(num_slots)]


def synthetic_device_batch(batch_size: int, num_dense: int,
                           slot_specs, sample_fixed_size: int = 1, seed=0):
    rng = np.random.default_rng(seed)
    non_id = [jnp.asarray(rng.normal(size=(batch_size, num_dense)),
                          jnp.float32)]
    ids = {
        name: jnp.asarray(
            rng.integers(1, 1 << 31, size=(batch_size, sample_fixed_size)),
            jnp.int32,
        )
        for name, _, _ in slot_specs
    }
    label = jnp.asarray(rng.integers(0, 2, size=(batch_size, 1)), jnp.float32)
    return non_id, ids, label
