"""Jitted train step for the device-resident embedding cache.

One compiled XLA program per step does ALL of: import this batch's
cache-miss rows (scatter), read back the rows they evict (gather, for
host write-back to the PS), embedding gather, dense forward/backward,
dense optimizer update, AND the sparse Adagrad update applied directly
to the cached rows on device. Nothing but miss rows and slot indices
crosses the host<->device wire — the hybrid path's per-step packed
upload/download (persia_tpu/parallel/train.py make_packed_train_step)
disappears for cache hits.

The sparse update mirrors the parameter server's decayed Adagrad
bit-for-bit in structure (persia_tpu/ps/optim.py SparseAdagrad,
non-shared; reference optim.rs:246-307): the step uses the accumulator
value from BEFORE this batch's gradient is accumulated, duplicate signs
within a batch contribute a summed gradient exactly like the
middleware's dedup+sum, and untouched rows keep their accumulator
(no decay without a gradient — same as rows the PS never sees).

Host-side mapping/eviction policy lives in
persia_tpu/worker/device_cache.py; the orchestration tying both to
TrainCtx is persia_tpu/parallel/cached_engine.py.

The steps' operations carry the scopes ``cache_import``, ``cache_gather``,
``tower``, ``dense_update`` and ``row_adagrad`` in their metadata, for a
trace to group them by.
"""

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from persia_tpu.parallel.train import (
    TrainState,
    _rebuild_embedding_inputs,
    bce_loss,
)


def _row_sharding(mesh):
    """Cache rows sharded over EVERY mesh device (data x model): the
    cache is ONE logical array partitioned by GSPMD, so per-row HBM
    scales with the device count and there is no per-trainer fork of
    optimizer state to reconcile — the single-writer invariant holds
    because there is a single (partitioned) program, XLA inserting the
    gather/scatter collectives."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def init_cache_arrays(capacity: int, dim: int, acc_init: float, mesh=None):
    """(rows, dim) value + accumulator arrays; row ``capacity`` is the
    dummy slot that padded miss entries target (writes land there and
    are never read). Under a mesh the row count is padded up to a
    multiple of the device count and the arrays are laid out with
    :func:`_row_sharding` (pad rows beyond the dummy are never
    addressed)."""
    rows = capacity + 1
    if mesh is not None:
        n_dev = int(np.prod(list(mesh.shape.values())))
        rows += (-rows) % n_dev
    vals = jnp.zeros((rows, dim), jnp.float32)
    acc = jnp.full((rows, dim), acc_init, jnp.float32)
    if mesh is not None:
        s = _row_sharding(mesh)
        vals, acc = jax.device_put(vals, s), jax.device_put(acc, s)
    return vals, acc


def _constrain_rows(mesh, cache_vals, cache_acc):
    """Pin the carried cache arrays to the row sharding (entry AND exit
    of each step: the donated output's sharding must match the input's
    for true in-place reuse)."""
    if mesh is None:
        return cache_vals, cache_acc
    s = _row_sharding(mesh)
    return (jax.lax.with_sharding_constraint(cache_vals, s),
            jax.lax.with_sharding_constraint(cache_acc, s))


def _import_cold(cache_vals, cache_acc, cold_idx, cold_vals, cold_acc):
    """Read the rows being evicted BEFORE their slots are reused, then
    write-allocate this batch's miss rows (pads target the dummy row)."""
    with jax.named_scope("cache_import"):
        evicted_vals = cache_vals[cold_idx]
        evicted_acc = cache_acc[cold_idx]
        cache_vals = cache_vals.at[cold_idx].set(cold_vals)
        cache_acc = cache_acc.at[cold_idx].set(cold_acc)
    return cache_vals, cache_acc, evicted_vals, evicted_acc


def _forward_backward(model, loss_fn, state, non_id_tensors, label,
                      gathered, emb_values_of):
    """Shared dense forward/backward: differentiates w.r.t. params AND
    the raw ``gathered`` embedding tensor (``emb_values_of`` maps it to
    the model's per-slot inputs inside the loss so autodiff routes any
    scaling into the embedding gradient)."""

    def compute_loss(params, gathered):
        variables = {"params": params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        with jax.named_scope("tower"):
            emb_values = emb_values_of(gathered)
            emb_inputs = _rebuild_embedding_inputs(
                emb_values, [None] * len(emb_values))
            out = model.apply(
                variables, non_id_tensors, emb_inputs, train=True,
                mutable=["batch_stats"] if state.batch_stats else [],
            )
        pred, mutated = out if isinstance(out, tuple) else (out, {})
        return loss_fn(pred, label), (pred, mutated)

    grad_fn = jax.value_and_grad(compute_loss, argnums=(0, 1),
                                 has_aux=True)
    return grad_fn(state.params, gathered)


def _dense_update(optimizer, state, param_grads, mutated):
    with jax.named_scope("dense_update"):
        updates, new_opt_state = optimizer.update(
            param_grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
    return TrainState(
        params=new_params,
        batch_stats=mutated.get("batch_stats", state.batch_stats),
        opt_state=new_opt_state,
        step=state.step + 1,
    )


def _sparse_adagrad_update(cache_vals, cache_acc, unique_slots, inverse,
                           pos_grad, dummy, dim, lr, eps,
                           g_square_momentum, weight_bound):
    """Sparse Adagrad on device, touching ONLY this batch's rows and
    allocating ONLY O(batch)-sized buffers: per-position gradients
    dedup-sum through the mapper's inverse map (== middleware
    dedup+sum) into an (Lpad, D) buffer — NOT a dense (capacity, D)
    one, which would cost a full-cache zero-init + memory pass per
    step. One optimizer row per distinct sign, scatter-SET back (pad
    rows carry zero grads and write their unchanged dummy-row value;
    untouched cache rows are never read or written — matching the PS:
    no accumulator decay without a gradient). The accumulator used is
    the PRE-update one, and the weight bound clamps after every update
    (ps/optim.py apply_weight_bound; reference persia-simd
    lib.rs:231-251) — mirror of the PS math, or cached and uncached
    training diverge."""
    with jax.named_scope("row_adagrad"):
        valid = (unique_slots != dummy)[:, None]
        gsum_u = jnp.zeros((inverse.shape[0], dim), jnp.float32).at[
            inverse].add(pos_grad)
        acc_u = cache_acc[unique_slots]
        new_val_u = (cache_vals[unique_slots]
                     - lr * gsum_u * jax.lax.rsqrt(acc_u + eps))
        if weight_bound > 0:
            new_val_u = jnp.clip(new_val_u, -weight_bound, weight_bound)
        new_acc_u = jnp.where(
            valid, acc_u * g_square_momentum + gsum_u * gsum_u, acc_u)
        cache_vals = cache_vals.at[unique_slots].set(new_val_u)
        cache_acc = cache_acc.at[unique_slots].set(new_acc_u)
    return cache_vals, cache_acc


def make_cached_train_step(
    model,
    optimizer: optax.GradientTransformation,
    num_slots: int,
    dim: int,
    lr: float,
    eps: float,
    g_square_momentum: float,
    loss_fn: Callable = bce_loss,
    weight_bound: float = 0.0,
    capacity: int = 0,
    mesh=None,
) -> Callable:
    """step(state, cache_vals, cache_acc, non_id, slot_idx, cold_idx,
    cold_vals, cold_acc, inverse, unique_slots, label) -> (state,
    cache_vals, cache_acc, loss, pred, evicted_vals, evicted_acc)

    - slot_idx: (B, S) int32 — cache slot per (sample, slot) position;
    - cold_idx: (M,) int32 — slots receiving this batch's miss rows
      (padded entries point at the dummy slot);
    - cold_vals/cold_acc: (M, D) — miss rows (+ Adagrad state) fetched
      from the PS / victim buffer;
    - inverse: (B*S,) int32 — position -> index among this batch's
      distinct signs (the mapper computes it during its probe pass);
    - unique_slots: (B*S,) int32 — distinct index -> cache slot, tail
      past the distinct count padded with the dummy slot;
    - evicted_vals/evicted_acc: (M, D) — the PREVIOUS contents of
      cold_idx slots, read before the overwrite; the host writes these
      back to the PS keyed by the evicted signs.

    This is the single-id FAST path: a pure gather feeds the model, no
    segment scatter-add (see :func:`make_cached_bag_train_step` for
    variable-length bags).
    """

    def step(state: TrainState, cache_vals, cache_acc, non_id_tensors,
             slot_idx, cold_idx, cold_vals, cold_acc, inverse,
             unique_slots, label):
        cache_vals, cache_acc = _constrain_rows(mesh, cache_vals,
                                                cache_acc)
        cache_vals, cache_acc, evicted_vals, evicted_acc = _import_cold(
            cache_vals, cache_acc, cold_idx, cold_vals, cold_acc)

        with jax.named_scope("cache_gather"):
            gathered = cache_vals[slot_idx]  # (B, S, D)
        (loss, (pred, mutated)), (param_grads, emb_grad) = \
            _forward_backward(
                model, loss_fn, state, non_id_tensors, label, gathered,
                lambda g: [g[:, i, :] for i in range(num_slots)])
        new_state = _dense_update(optimizer, state, param_grads, mutated)

        # the dummy row sits at index `capacity` (NOT rows-1: under a
        # mesh the row count is padded past the dummy for even sharding)
        dummy = capacity if capacity else cache_vals.shape[0] - 1
        cache_vals, cache_acc = _sparse_adagrad_update(
            cache_vals, cache_acc, unique_slots, inverse,
            emb_grad.reshape(-1, dim), dummy, dim, lr, eps,
            g_square_momentum, weight_bound)
        cache_vals, cache_acc = _constrain_rows(mesh, cache_vals,
                                                cache_acc)
        return (new_state, cache_vals, cache_acc, loss, pred,
                evicted_vals, evicted_acc)

    # donate the cache arrays: they are carried state, updated in place
    return jax.jit(step, donate_argnums=(1, 2))


def make_cached_bag_train_step(
    model,
    optimizer: optax.GradientTransformation,
    num_slots: int,
    dim: int,
    lr: float,
    eps: float,
    g_square_momentum: float,
    loss_fn: Callable = bce_loss,
    weight_bound: float = 0.0,
    capacity: int = 0,
    mesh=None,
) -> Callable:
    """Multi-id (bag) variant of :func:`make_cached_train_step`.

    Every slot is a summed bag of variable length; the host flattens all
    (sample, slot) bags into one position list (length L, bucket-padded
    to Lpad) with a segment id per position. On device:

    - gather rows per position, segment-sum into per-(sample, slot)
      bags (matching the middleware's segment sum,
      worker/middleware.py postprocess_feature);
    - ``scale`` (B, S) applies sqrt_scaling (1/sqrt(bag size)) INSIDE
      the loss so autodiff routes the same scaling into the gradients
      (matching aggregate_gradients);
    - the backward re-gathers per-position grads through the segment
      map and dedup-sums them per distinct sign via ``inverse`` — a
      sign appearing twice in one bag contributes twice, exactly like
      the middleware's occurrence-level segment sum.

    step(state, cache_vals, cache_acc, non_id, flat_slot_idx (Lpad,),
    seg (Lpad,), scale (B, S), cold_idx, cold_vals, cold_acc,
    inverse (Lpad,), unique_slots (Lpad,), label) -> same outputs as
    the single-id step. Pad positions carry seg == B*S (a trash bag
    row) and flat_slot_idx == dummy, making them inert in both passes.
    """

    def step(state: TrainState, cache_vals, cache_acc, non_id_tensors,
             flat_slot_idx, seg, scale, cold_idx, cold_vals, cold_acc,
             inverse, unique_slots, label):
        cache_vals, cache_acc = _constrain_rows(mesh, cache_vals,
                                                cache_acc)
        cache_vals, cache_acc, evicted_vals, evicted_acc = _import_cold(
            cache_vals, cache_acc, cold_idx, cold_vals, cold_acc)

        batch = label.shape[0]
        with jax.named_scope("cache_gather"):
            rows = cache_vals[flat_slot_idx]               # (Lpad, D)
            bags = jnp.zeros((batch * num_slots + 1, dim),
                             jnp.float32).at[seg].add(rows)
            gathered = bags[:batch * num_slots].reshape(
                batch, num_slots, dim)

        def emb_values_of(g):
            scaled = g * scale[:, :, None]
            return [scaled[:, i, :] for i in range(num_slots)]

        (loss, (pred, mutated)), (param_grads, bag_grad) = \
            _forward_backward(model, loss_fn, state, non_id_tensors,
                              label, gathered, emb_values_of)
        new_state = _dense_update(optimizer, state, param_grads, mutated)

        # per-position grads: pad positions (seg == B*S) read the zero
        # trash row, so their contribution to the dedup-sum is zero
        gpad = jnp.concatenate(
            [bag_grad.reshape(-1, dim), jnp.zeros((1, dim), jnp.float32)])
        pos_grad = gpad[seg]                               # (Lpad, D)
        dummy = capacity if capacity else cache_vals.shape[0] - 1
        cache_vals, cache_acc = _sparse_adagrad_update(
            cache_vals, cache_acc, unique_slots, inverse, pos_grad,
            dummy, dim, lr, eps, g_square_momentum, weight_bound)
        cache_vals, cache_acc = _constrain_rows(mesh, cache_vals,
                                                cache_acc)
        return (new_state, cache_vals, cache_acc, loss, pred,
                evicted_vals, evicted_acc)

    return jax.jit(step, donate_argnums=(1, 2))


def make_cached_eval_step(model, num_slots: int) -> Callable:
    """Pure gather + forward for signs fully resident in the cache."""

    def step(state: TrainState, cache_vals, non_id_tensors, slot_idx):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        gathered = cache_vals[slot_idx]
        emb_values = [gathered[:, i, :] for i in range(num_slots)]
        emb_inputs = _rebuild_embedding_inputs(emb_values, [None] * num_slots)
        return model.apply(variables, non_id_tensors, emb_inputs, train=False)

    return jax.jit(step)


def pad_to_bucket(n: int, buckets: Sequence[int]) -> int:
    """Pad a miss count to a fixed size so jit reuses a few compiled
    geometries instead of recompiling per distinct count."""
    for b in buckets:
        if n <= b:
            return b
    return int(np.ceil(n / buckets[-1]) * buckets[-1])
