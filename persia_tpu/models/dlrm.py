"""DLRM dense tower: bottom MLP + pairwise dot interactions + top MLP.

The canonical benchmark model for this framework's north-star metric
(Criteo DLRM samples/sec/chip; PERF.md). Interaction is the standard
lower-triangle pairwise dot of field embeddings + the bottom-MLP output,
computed as one batched matmul so it lands on the MXU.
"""

from typing import Any, Sequence

import jax.numpy as jnp
from flax import linen as nn

from persia_tpu.models.common import MLP, stack_field_embeddings


class DLRM(nn.Module):
    embedding_dim: int = 16
    bottom_mlp: Sequence[int] = (64, 32)
    top_mlp: Sequence[int] = (256, 128)
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, non_id_tensors: Sequence[jnp.ndarray],
                 embedding_tensors: Sequence[Any], train: bool = False):
        dt = self.compute_dtype
        dense_x = non_id_tensors[0].astype(dt)
        bottom = MLP((*self.bottom_mlp, self.embedding_dim),
                     compute_dtype=dt)(dense_x, train)

        fields = stack_field_embeddings(embedding_tensors).astype(dt)
        # (bs, F+1, d): dense projection joins the interaction
        t = jnp.concatenate([bottom[:, None, :], fields], axis=1)
        # pairwise dots on the MXU: (bs, F+1, F+1)
        dots = jnp.einsum("bfd,bgd->bfg", t, t)
        f = t.shape[1]
        iu, ju = jnp.triu_indices(f, k=1)
        interactions = dots[:, iu, ju]

        top_in = jnp.concatenate([bottom, interactions.astype(dt)], axis=1)
        out = MLP((*self.top_mlp, 1), final_activation=False,
                  compute_dtype=dt)(top_in, train)
        return nn.sigmoid(out.astype(jnp.float32))
