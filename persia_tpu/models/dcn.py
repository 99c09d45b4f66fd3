"""DCN-v2: deep & cross network with full-matrix cross layers.

One of the reference workload's alternate dense towers. Cross layers compute
``x_{l+1} = x0 * (W_l x_l + b_l) + x_l`` (the v2 formulation) with the
matmul in bf16 on the MXU.
"""

from typing import Any, Sequence

import jax.numpy as jnp
from flax import linen as nn

from persia_tpu.models.common import MLP, flatten_embeddings


class CrossLayer(nn.Module):
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x0, x):
        w = nn.Dense(x0.shape[-1], dtype=self.compute_dtype)(x)
        return x0 * w + x


class DCNv2(nn.Module):
    num_cross_layers: int = 3
    deep_mlp: Sequence[int] = (256, 128)
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, non_id_tensors: Sequence[jnp.ndarray],
                 embedding_tensors: Sequence[Any], train: bool = False):
        dt = self.compute_dtype
        parts = [t.astype(dt) for t in non_id_tensors]
        parts.append(flatten_embeddings(embedding_tensors).astype(dt))
        x0 = jnp.concatenate(parts, axis=1)

        x = x0
        for _ in range(self.num_cross_layers):
            x = CrossLayer(compute_dtype=dt)(x0, x)

        deep = MLP(self.deep_mlp, compute_dtype=dt)(x0, train)
        combined = jnp.concatenate([x, deep], axis=1)
        out = nn.Dense(1, dtype=dt)(combined)
        return nn.sigmoid(out.astype(jnp.float32))
