"""Device-resident sharded embedding tables — the TPU-first sparse mode.

The CUDA reference keeps all embeddings on CPU parameter servers because
GPU HBM is too small for 100T parameters. On TPU pods, a second mode is
natural: hash the sign space into a fixed-vocab table that lives in HBM,
sharded row-wise over the mesh's ``model`` axis. Lookup is a gather that
XLA turns into collective-permute traffic over ICI; gradients flow through
ordinary autodiff (scatter-add) and the table trains with the same optax
transformation as the dense tower — no host round-trip at all.

Use this mode when the (hashed) vocab fits in pod HBM; use the CPU
parameter-server mode for beyond-HBM scale. Both share the worker
preprocessing (dedup/prefix) and the model zoo.
"""

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from persia_tpu.parallel.mesh import MODEL_AXIS


class DeviceEmbeddingBag(nn.Module):
    """One hashed embedding table with sum/mean pooling.

    ids enter as the worker's static-shape (bs, sample_fixed_size) index
    tensor of raw u64 signs hashed modulo ``vocab_size`` (0 rows are
    reserved for padding via the mask argument).
    """

    vocab_size: int
    dim: int
    compute_dtype: Any = jnp.bfloat16
    pooling: str = "sum"  # "sum" | "mean"

    @nn.compact
    def __call__(self, hashed_ids: jnp.ndarray, mask: jnp.ndarray):
        table = self.param(
            "table",
            nn.with_partitioning(
                nn.initializers.uniform(scale=0.01), (MODEL_AXIS, None)
            ),
            (self.vocab_size, self.dim),
            jnp.float32,
        )
        # the name a trace knows table work by; the backward scatter
        # carries it too, under transpose(jvp(...))
        with jax.named_scope("tables_gather"):
            gathered = jnp.take(table, hashed_ids, axis=0)  # (bs, sfs, dim)
            gathered = gathered * mask[..., None].astype(gathered.dtype)
            pooled = gathered.sum(axis=1)
            if self.pooling == "mean":
                denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1)
                pooled = pooled / denom
            return pooled.astype(self.compute_dtype)


class DeviceEmbeddingCollection(nn.Module):
    """All slots' device tables, producing the model-ready embedding list.

    ``slot_specs`` is a sequence of (name, vocab_size, dim). Input is a
    dict name -> (bs, sfs) int32/uint32 hashed id tensor; id 0 = padding.
    """

    slot_specs: Sequence[Any]
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, id_tensors):
        out = []
        for name, vocab, dim in self.slot_specs:
            ids = id_tensors[name]
            mask = ids > 0
            hashed = (ids % (vocab - 1)) + 1  # row 0 reserved for padding
            bag = DeviceEmbeddingBag(
                vocab_size=vocab, dim=dim, compute_dtype=self.compute_dtype,
                name=f"bag_{name}",
            )
            out.append(bag(hashed * mask, mask))
        return out
