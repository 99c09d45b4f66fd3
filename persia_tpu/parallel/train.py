"""The jitted hybrid train/eval step.

This is the TPU re-design of the reference's TrainCtx forward/backward
machinery (persia/ctx.py:893-1005): one compiled XLA program computes the
dense forward, the loss, the dense-parameter update, **and the gradients
w.r.t. the embedding inputs**, which exit the step as ordinary outputs and
are routed back to the parameter servers by the host (the async sparse
path). No GradScaler: bf16 compute has f32 exponent range, so the finite
check is a cheap debug hook rather than a correctness requirement.

Embedding inputs are split into differentiable values (float arrays) and
static index tensors (raw-slot int32 indices) so ``jax.grad`` sees only
float leaves.
"""

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct


@struct.dataclass
class TrainState:
    params: Any
    batch_stats: Any
    opt_state: Any
    step: jnp.ndarray


def bce_loss(pred: jnp.ndarray, label: jnp.ndarray) -> jnp.ndarray:
    """Binary cross entropy on sigmoid outputs (adult-income parity)."""
    pred = jnp.clip(pred, 1e-7, 1.0 - 1e-7)
    return -jnp.mean(label * jnp.log(pred) + (1.0 - label) * jnp.log(1.0 - pred))


def next_item_cross_entropy(logits: jnp.ndarray,
                            target: jnp.ndarray) -> jnp.ndarray:
    """Softmax cross entropy of each position's (..., classes) logits
    against the class the next event took, a mean over the positions
    that have one (``target`` < 0: none, as the last position of a
    history whose next event lies outside the batch). Float32
    throughout."""
    valid = target >= 0
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, target, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * valid) / jnp.maximum(jnp.sum(valid), 1)


def next_items_cross_entropy(logits, target: jnp.ndarray,
                             ahead_weight: float = 0.3) -> jnp.ndarray:
    """The loss of a tower with one multi-token-prediction module:
    ``logits`` is the pair (main head's, module's), position t of the
    first against the next event's class as above, of the second against
    the class of the event after next, which is ``target`` shifted left
    by one with nothing for a history's last position; the module's mean
    over its own positions counts ``ahead_weight`` times (the family's
    published lambda is 0.3 early in training)."""
    main, ahead = logits
    shifted = jnp.concatenate(
        [target[..., 1:], jnp.full_like(target[..., :1], -1)], axis=-1)
    return (next_item_cross_entropy(main, target)
            + ahead_weight * next_item_cross_entropy(ahead, shifted))


def next_item_cross_entropy_indexed(outputs, target: jnp.ndarray,
                                    index_loss_weight: float = 1.0
                                    ) -> jnp.ndarray:
    """The loss of a tower whose attention is over keys an indexer
    selects: ``outputs`` is the pair (logits, the indexer's alignment
    loss summed over the tower's layers); the cross entropy as above
    plus ``index_loss_weight`` times that term, which is the only one
    the indexer's parameters learn from."""
    logits, index_loss = outputs
    return (next_item_cross_entropy(logits, target)
            + index_loss_weight * index_loss)


def _rebuild_embedding_inputs(
    emb_values: Sequence[jnp.ndarray], emb_indices: Sequence[Optional[jnp.ndarray]]
) -> List[Any]:
    return [
        v if idx is None else (v, idx)
        for v, idx in zip(emb_values, emb_indices)
    ]


def create_train_state(
    model, optimizer: optax.GradientTransformation, rng,
    non_id_tensors, embedding_inputs,
) -> TrainState:
    emb_values, emb_indices = split_embedding_inputs(embedding_inputs)
    variables = model.init(
        rng, non_id_tensors,
        _rebuild_embedding_inputs(emb_values, emb_indices), train=False,
    )
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(
        params=params,
        batch_stats=batch_stats,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
    )


def split_embedding_inputs(embedding_inputs: Sequence[Any]):
    """Split mixed [array | (array, index)] inputs into float values and
    optional index tensors (None for summed slots)."""
    values, indices = [], []
    for e in embedding_inputs:
        if isinstance(e, (tuple, list)):
            values.append(e[0])
            indices.append(e[1])
        else:
            values.append(e)
            indices.append(None)
    return values, indices


def make_train_step(
    model,
    optimizer: optax.GradientTransformation,
    loss_fn: Callable = bce_loss,
) -> Callable:
    """Build the jitted train step.

    step(state, non_id_tensors, emb_values, emb_indices, label)
      -> (state, loss, emb_grads, pred)

    ``emb_indices`` entries must be None or int32 arrays; they are part of
    the traced input pytree, not captured constants, so raw-slot index
    tensors change per batch without retracing.
    """

    def step(state: TrainState, non_id_tensors, emb_values, emb_indices, label):
        def compute_loss(params, emb_values):
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            emb_inputs = _rebuild_embedding_inputs(emb_values, emb_indices)
            out = model.apply(
                variables, non_id_tensors, emb_inputs, train=True,
                mutable=["batch_stats"] if state.batch_stats else [],
            )
            pred, mutated = out if isinstance(out, tuple) else (out, {})
            loss = loss_fn(pred, label)
            return loss, (pred, mutated)

        grad_fn = jax.value_and_grad(compute_loss, argnums=(0, 1), has_aux=True)
        (loss, (pred, mutated)), (param_grads, emb_grads) = grad_fn(
            state.params, emb_values
        )
        updates, new_opt_state = optimizer.update(
            param_grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=new_params,
            batch_stats=mutated.get("batch_stats", state.batch_stats),
            opt_state=new_opt_state,
            step=state.step + 1,
        )
        return new_state, loss, emb_grads, pred

    return jax.jit(step)


def make_packed_train_step(
    model,
    optimizer: optax.GradientTransformation,
    emb_shapes: Sequence[Tuple[int, ...]],
    loss_fn: Callable = bce_loss,
    wire_dtype=jnp.bfloat16,
) -> Callable:
    """Train step with **packed** embedding I/O for host-PS mode.

    All slots' embedding values enter as ONE flat ``wire_dtype`` array and
    all embedding gradients leave as ONE flat ``wire_dtype`` array — a
    single host->device and device->host transfer per step instead of one
    per slot. This is the TPU analogue of the reference's f16 wire format
    (persia-common/src/lib.rs:85-113) and matters enormously when the
    host<->device link has per-transfer latency.

    ``emb_shapes`` fixes each slot's (rows, dim); changing batch size
    retraces (shapes are static under XLA).

    step(state, non_id, flat_emb, emb_indices, label)
      -> (state, loss, flat_grads, pred)
    """
    sizes = [int(np.prod(s)) for s in emb_shapes]
    offsets = np.concatenate([[0], np.cumsum(sizes)]).tolist()

    def step(state: TrainState, non_id_tensors, flat_emb, emb_indices, label):
        emb_values = [
            flat_emb[offsets[i] : offsets[i + 1]]
            .reshape(emb_shapes[i])
            .astype(jnp.float32)
            for i in range(len(emb_shapes))
        ]

        def compute_loss(params, emb_values):
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            emb_inputs = _rebuild_embedding_inputs(emb_values, emb_indices)
            out = model.apply(
                variables, non_id_tensors, emb_inputs, train=True,
                mutable=["batch_stats"] if state.batch_stats else [],
            )
            pred, mutated = out if isinstance(out, tuple) else (out, {})
            loss = loss_fn(pred, label)
            return loss, (pred, mutated)

        grad_fn = jax.value_and_grad(compute_loss, argnums=(0, 1), has_aux=True)
        (loss, (pred, mutated)), (param_grads, emb_grads) = grad_fn(
            state.params, emb_values
        )
        updates, new_opt_state = optimizer.update(
            param_grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=new_params,
            batch_stats=mutated.get("batch_stats", state.batch_stats),
            opt_state=new_opt_state,
            step=state.step + 1,
        )
        flat_grads = jnp.concatenate(
            [g.ravel() for g in emb_grads]
        ).astype(wire_dtype)
        return new_state, loss, flat_grads, pred

    return jax.jit(step, donate_argnums=(0,))


# int8_ef quantization bucket: one f32 scale per this many elements.
# Scale overhead on the wire is 4B/1024B ≈ 0.4%; accuracy gain is large
# whenever parameter groups differ in gradient magnitude (one outlier
# layer no longer crushes every other layer's resolution).
_EF_BUCKET = 1024


def _ef_int8_mean(p: jnp.ndarray, axis_name: str, world: int):
    """Two-phase int8-compressed gradient mean over ``axis_name``.

    The TPU survivor of the reference's Bagua family (ByteGrad/QAdam,
    /root/reference/persia/distributed.py:204-410): on ICI a plain bf16
    pmean already wins, but on multi-host DCN meshes the wire is the
    bottleneck and 4x fewer bytes buys real throughput. Scheme:

    1. quantize the (error-compensated) local gradient to int8 with a
       per-replica scale per 1024-element bucket (``_EF_BUCKET``); the
       vector is zero-padded to a multiple of ``world * _EF_BUCKET`` so
       buckets never straddle shard boundaries;
    2. ``all_to_all`` the int8 shards AND their bucket scales (each
       device receives every replica's copy of ITS shard — int8 plus
       ~0.4% of scale floats on the wire), dequantize per bucket, sum
       in f32;
    3. requantize the mean shard per bucket and ``all_gather`` it back
       with its scales.

    Total wire bytes ~= 2 x size x 1B x 1.004 vs 2 x size x 4B for a
    ring f32 all-reduce. BOTH quantization stages feed back into ``err``
    (error-feedback SGD: the residual re-enters the next step's
    gradient, so the bias of deterministic rounding averages out and
    convergence tracks the uncompressed trajectory): stage 1 locally on
    every replica; stage 2 by the shard's owner, scaled by ``world``
    because a mean error times world is the aggregate error the owner
    must re-inject through its own (1/world-weighted) contribution.

    ``p``: f32 vector (grad + carried error). Returns (mean, new_err),
    both f32 of p's shape.
    """
    n = p.shape[0]
    pad = (-n) % (world * _EF_BUCKET)
    flat = jnp.pad(p, (0, pad))
    chunk = flat.shape[0] // world          # shard length, % _EF_BUCKET == 0
    nb_per = chunk // _EF_BUCKET            # buckets per shard
    buckets = flat.reshape(world * nb_per, _EF_BUCKET)
    scale = jnp.maximum(
        jnp.max(jnp.abs(buckets), axis=1) / 127.0, 1e-30)  # (world*nb_per,)
    q = jnp.clip(jnp.round(buckets / scale[:, None]),
                 -127, 127).astype(jnp.int8)
    err1 = (buckets - q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    qs = q.reshape(world, chunk)
    # rows of recv are indexed by source replica: recv[s] = replica s's
    # int8 copy of THIS device's shard; srecv[s] = that copy's bucket
    # scales (all_to_all routes both identically)
    recv = jax.lax.all_to_all(qs, axis_name, split_axis=0, concat_axis=0)
    srecv = jax.lax.all_to_all(scale.reshape(world, nb_per), axis_name,
                               split_axis=0, concat_axis=0)
    deq = (recv.reshape(world, nb_per, _EF_BUCKET).astype(jnp.float32)
           * srecv[:, :, None])
    shard_mean = jnp.sum(deq, axis=0).reshape(chunk) / world
    mb = shard_mean.reshape(nb_per, _EF_BUCKET)
    s2 = jnp.maximum(jnp.max(jnp.abs(mb), axis=1) / 127.0, 1e-30)
    q2 = jnp.clip(jnp.round(mb / s2[:, None]), -127, 127).astype(jnp.int8)
    # stage-2 residual: this device owns shard `me` of the decoded mean
    err2 = (mb - q2.astype(jnp.float32) * s2[:, None]).reshape(chunk) * world
    me = jax.lax.axis_index(axis_name)
    own = jax.lax.dynamic_slice(err1, (me * chunk,), (chunk,))
    new_err = jax.lax.dynamic_update_slice(
        err1, own + err2, (me * chunk,))[:n]
    q2g = jax.lax.all_gather(q2, axis_name)   # (world, nb_per, _EF_BUCKET)
    s2g = jax.lax.all_gather(s2, axis_name)   # (world, nb_per)
    mean = (q2g.astype(jnp.float32) * s2g[:, :, None]).reshape(-1)[:n]
    return mean, new_err


def init_ef_state(params, mesh) -> jnp.ndarray:
    """Zero error-feedback residuals for ``grad_reduce_dtype="int8_ef"``:
    one flat f32 vector of the dense-param count per data-parallel
    replica, carried through the DDP step sharded over the data axis
    (each replica's residual is ITS OWN quantization error — it must
    not be replicated). Built under an explicit NamedSharding so a
    multi-host mesh (the mode's stated target) gets a global array, not
    a host-local one jit would refuse to reshard."""
    from jax.flatten_util import ravel_pytree

    from persia_tpu.parallel.mesh import DATA_AXIS, batch_sharding

    flat, _ = ravel_pytree(params)
    world = mesh.shape[DATA_AXIS]
    # computed UNDER the sharding (not device_put of a host-local
    # array, which would raise on a multi-process mesh's
    # non-addressable devices)
    return jax.jit(
        lambda: jnp.zeros((world, flat.shape[0]), jnp.float32),
        out_shardings=batch_sharding(mesh))()


def make_packed_train_step_ddp(
    model,
    optimizer: optax.GradientTransformation,
    slot_dims: Sequence[int],
    mesh,
    loss_fn: Callable = bce_loss,
    wire_dtype=jnp.bfloat16,
    grad_reduce_dtype=None,
) -> Callable:
    """Explicit data-parallel train step over a mesh via ``shard_map``.

    The reference offers DDP plus Bagua's communication algorithms
    (gradient_allreduce / low-precision variants,
    persia/distributed.py:204-410). The TPU equivalent is explicit
    collectives: each device computes gradients on its batch shard and
    the dense gradients cross ICI in ``jax.lax.pmean`` — optionally cast
    to ``grad_reduce_dtype`` (e.g. ``jnp.bfloat16``) first, halving
    all-reduce bytes the way Bagua's low-precision algorithms do.
    ``grad_reduce_dtype="int8_ef"`` goes further: an error-feedback
    int8 two-phase all-reduce (see :func:`_ef_int8_mean`) cutting wire
    bytes 4x — the Bagua ByteGrad analogue for multi-host DCN meshes.
    In that mode the step takes and returns an extra ``ef_state``
    residual (build with :func:`init_ef_state`). Decentralized/async
    peer algorithms have no XLA analogue and are deliberately absent:
    ICI all-reduce is already the fast path the reference's algorithms
    try to approximate.

    Requires every slot to be summed (pooled): embedding values enter
    batch-major as ONE ``(batch, sum(slot_dims))`` wire array so the
    batch axis shards cleanly. ``step(state, non_id, flat_emb,
    label) -> (state, loss, flat_grads, pred)`` with ``flat_grads``
    batch-major ``(batch, sum(slot_dims))`` in the wire dtype.
    """
    from jax.sharding import PartitionSpec as P

    from persia_tpu.parallel.ring_attention import _shard_map

    bounds = np.concatenate([[0], np.cumsum(slot_dims)]).tolist()
    data_spec = P("data")
    rep = P()
    ef_mode = grad_reduce_dtype == "int8_ef"
    from persia_tpu.parallel.mesh import DATA_AXIS

    world = mesh.shape[DATA_AXIS]

    def local_step(state: TrainState, non_id_tensors, flat_emb, label,
                   ef_state=None):
        emb_values = [
            flat_emb[:, bounds[i]:bounds[i + 1]].astype(jnp.float32)
            for i in range(len(slot_dims))
        ]

        def compute_loss(params, emb_values):
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            emb_inputs = _rebuild_embedding_inputs(
                emb_values, [None] * len(emb_values))
            out = model.apply(
                variables, non_id_tensors, emb_inputs, train=True,
                mutable=["batch_stats"] if state.batch_stats else [],
            )
            pred, mutated = out if isinstance(out, tuple) else (out, {})
            loss = loss_fn(pred, label)
            return loss, (pred, mutated)

        grad_fn = jax.value_and_grad(compute_loss, argnums=(0, 1),
                                     has_aux=True)
        (loss, (pred, mutated)), (param_grads, emb_grads) = grad_fn(
            state.params, emb_values
        )
        # the cross-replica exchange: dense grads ride ICI, optionally in
        # reduced precision (cast -> pmean -> f32, Bagua low-prec analogue)
        # or int8 with error feedback (ByteGrad analogue, 4x fewer bytes)
        if ef_mode:
            from jax.flatten_util import ravel_pytree

            flat_g, unravel = ravel_pytree(param_grads)
            mean_flat, new_err = _ef_int8_mean(
                flat_g + ef_state[0], "data", world)
            param_grads = unravel(mean_flat)
            new_ef_state = new_err[None, :]
        else:
            if grad_reduce_dtype is not None:
                param_grads = jax.tree_util.tree_map(
                    lambda g: g.astype(grad_reduce_dtype), param_grads)
            param_grads = jax.lax.pmean(param_grads, axis_name="data")
            if grad_reduce_dtype is not None:
                param_grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), param_grads)
        loss = jax.lax.pmean(loss, axis_name="data")
        if mutated:
            # BatchNorm running stats are computed per batch shard;
            # average them so every replica keeps identical buffers
            mutated = jax.lax.pmean(mutated, axis_name="data")
        # embedding grads are per-sample: they exit batch-sharded, no
        # collective needed (the async PS path owns their reduction)
        updates, new_opt_state = optimizer.update(
            param_grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=new_params,
            batch_stats=mutated.get("batch_stats", state.batch_stats),
            opt_state=new_opt_state,
            step=state.step + 1,
        )
        flat_grads = jnp.concatenate(emb_grads, axis=1).astype(wire_dtype)
        if ef_mode:
            return new_state, loss, flat_grads, pred, new_ef_state
        return new_state, loss, flat_grads, pred

    extra = (data_spec,) if ef_mode else ()
    sharded = _shard_map(
        local_step, mesh,
        in_specs=(rep, data_spec, data_spec, data_spec) + extra,
        out_specs=(rep, rep, data_spec, data_spec) + extra,
    )
    return jax.jit(sharded, donate_argnums=(0, 4) if ef_mode else (0,))


def pack_embedding_values_batch_major(
    emb_values: Sequence[np.ndarray], wire_dtype
) -> np.ndarray:
    """(batch, dim_i) summed-slot values -> one (batch, sum dims) array."""
    import ml_dtypes

    np_dtype = (
        ml_dtypes.bfloat16 if wire_dtype == jnp.bfloat16 else np.float32
    )
    flat = np.concatenate(
        [np.ascontiguousarray(v, dtype=np.float32) for v in emb_values],
        axis=1,
    )
    return flat.astype(np_dtype)


def unpack_embedding_grads_batch_major(
    flat: np.ndarray, slot_dims: Sequence[int]
) -> List[np.ndarray]:
    """(batch, sum dims) gradient blob -> per-slot (batch, dim_i) f32."""
    flat = np.asarray(flat)
    out = []
    pos = 0
    for d in slot_dims:
        out.append(flat[:, pos:pos + d].astype(np.float32))
        pos += d
    return out


def pack_embedding_values(emb_values: Sequence[np.ndarray], wire_dtype):
    """Host-side pack: concat + cast for the single upload."""
    import ml_dtypes  # ships with jax

    np_dtype = (
        ml_dtypes.bfloat16 if wire_dtype == jnp.bfloat16 else np.float32
    )
    flat = np.concatenate(
        [np.ascontiguousarray(v, dtype=np.float32).ravel() for v in emb_values]
    )
    return flat.astype(np_dtype)


def unpack_embedding_grads(
    flat: np.ndarray, emb_shapes: Sequence[Tuple[int, ...]]
) -> List[np.ndarray]:
    """Host-side unpack of the single gradient download (to f32)."""
    out = []
    pos = 0
    flat = np.asarray(flat)
    for shape in emb_shapes:
        n = int(np.prod(shape))
        out.append(flat[pos : pos + n].astype(np.float32).reshape(shape))
        pos += n
    return out


def make_eval_step(model) -> Callable:
    def step(state: TrainState, non_id_tensors, emb_values, emb_indices):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        emb_inputs = _rebuild_embedding_inputs(emb_values, emb_indices)
        return model.apply(variables, non_id_tensors, emb_inputs, train=False)

    return jax.jit(step)
