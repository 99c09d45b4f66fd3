"""Dense model zoo (flax.linen, bf16-first).

Every model shares the calling convention of the reference's example
towers (examples/src/adult-income/model.py): ``model(non_id_tensors,
embedding_tensors, train=...)`` where embedding_tensors holds (bs, dim)
summed slots and (embeddings, index) raw pairs.
"""

from persia_tpu.models.common import (
    MLP,
    flatten_embeddings,
    gather_raw_embedding,
    stack_field_embeddings,
)
from persia_tpu.models.dcn import DCNv2
from persia_tpu.models.deepfm import DeepFM
from persia_tpu.models.dlrm import DLRM
from persia_tpu.models.dnn import DNN
from persia_tpu.models.hybrid_seq import HybridSequenceTower
from persia_tpu.models.seq import SequenceSelfAttention, SequenceTower
from persia_tpu.models.wide_deep import WideAndDeep

__all__ = [
    "MLP",
    "DNN",
    "DLRM",
    "DCNv2",
    "DeepFM",
    "HybridSequenceTower",
    "SequenceTower",
    "WideAndDeep",
    "SequenceSelfAttention",
    "flatten_embeddings",
    "gather_raw_embedding",
    "stack_field_embeddings",
]
