"""Fully device-resident training: dense tower + sharded HBM embeddings.

This mode composes :class:`DeviceEmbeddingCollection` (tables sharded over
the mesh's ``model`` axis) with any dense tower from the model zoo into a
single jitted train step — dense DP allreduce and embedding-shard
collectives are both XLA-inserted over ICI. It is the TPU-first
alternative to the CPU parameter-server path and the configuration the
multi-chip dry run exercises.
"""

from functools import partial
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax.core import meta
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from persia_tpu.models.dlrm import DLRM
from persia_tpu.parallel.mesh import batch_sharding, make_mesh, replicated
from persia_tpu.parallel.train import bce_loss


class DeviceModeModel(nn.Module):
    """Dense tower + device embedding tables as one module.

    ``slot_specs``: sequence of (name, vocab_size, dim) for the hashed
    HBM tables; ``tower``: a model-zoo module instance.
    """

    slot_specs: Sequence[Any]
    tower: nn.Module

    @nn.compact
    def __call__(self, non_id_tensors, id_tensors: Dict[str, jnp.ndarray],
                 train: bool = False):
        from persia_tpu.parallel.device_embedding import (
            DeviceEmbeddingCollection,
        )

        embs = DeviceEmbeddingCollection(slot_specs=self.slot_specs)(id_tensors)
        with jax.named_scope("tower"):
            return self.tower(non_id_tensors, embs, train=train)


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

    Returns (params, opt_state, step) where
    ``step(params, opt_state, non_id, ids, label) ->
    (params, opt_state, loss)``. Parameter shardings come from the
    modules' ``with_partitioning`` metadata; everything else replicates.
    The step's operations carry the scopes ``tables_gather``, ``tower``
    and ``optimizer`` in their metadata, for a trace to group them by.
    """
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
    opt_state = optimizer.init(params)

    def step(params, opt_state, non_id, ids, label):
        def compute_loss(params):
            pred = model.apply({"params": params}, non_id, ids, train=True)
            return loss_fn(pred, label)

        loss, grads = jax.value_and_grad(compute_loss)(params)
        with jax.named_scope("optimizer"):
            updates, opt_state2 = optimizer.update(grads, opt_state, params)
            params2 = optax.apply_updates(params, updates)
        return params2, opt_state2, loss

    return params, opt_state, jax.jit(step, donate_argnums=(0, 1))


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
