"""Device-mesh helpers: the TPU-native replacement for the reference's
DDP/NCCL process groups (persia/distributed.py:74-201).

A PERSIA-style job maps onto a 2-D mesh:

- ``data`` axis — synchronous data parallelism of the dense tower (the
  reference's DDP allreduce becomes an XLA psum over ICI)
- ``model`` axis — sharding of device-resident embedding tables (the
  TPU-first alternative to CPU parameter servers; CPU-PS mode uses a
  1-D data mesh)
"""

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from persia_tpu import tracing

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    shape: Optional[Tuple[int, int]] = None,
    axis_names: Tuple[str, str] = (DATA_AXIS, MODEL_AXIS),
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a (data, model) mesh over the available devices.

    Default shape puts every device on the data axis — pure DP, the
    reference's topology. Pass e.g. ``shape=(4, 2)`` for hybrid
    DP x embedding-sharding.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices), 1)
    if shape[0] * shape[1] != len(devices):
        raise ValueError(f"mesh shape {shape} != {len(devices)} devices")
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Shard embedding-table rows over the model axis."""
    return NamedSharding(mesh, P(MODEL_AXIS, None))


# the unsigned integer a packed buffer is made of, by item size
_WORD = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _batch_sharded(x, data_size: int) -> bool:
    return hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] % data_size == 0


@functools.lru_cache(maxsize=None)
def _unpacker(mesh: Mesh, layout):
    """The jitted function that cuts packed buffers back into leaves.

    ``layout`` holds, a buffer, the (shape, dtype) of every leaf in it,
    in the order their columns lie along the buffer's axis 0. Cached, so
    a batch signature compiles once.
    """

    def unpack(*buffers):
        leaves = []
        for buf, members in zip(buffers, layout):
            at = 0
            for shape, dtype in members:
                width = math.prod(shape[1:])
                x = lax.slice_in_dim(buf, at, at + width, axis=0)
                x = x.T.reshape(shape)
                if x.dtype != dtype:
                    x = lax.bitcast_convert_type(x, dtype)
                leaves.append(x)
                at += width
        return leaves

    n_leaves = sum(len(members) for members in layout)
    return jax.jit(unpack, out_shardings=[batch_sharding(mesh)] * n_leaves)


def shard_batch_pytree(tree, mesh: Mesh):
    """Put every leaf on the mesh with its batch dim over the data axis.

    Leaves whose leading dim does not divide the data-axis size are
    replicated instead — notably raw-slot distinct-embedding tensors of
    capacity batch*sample_fixed_size+1, which are indexed globally and
    must be visible to every data shard. Scalars are replicated.

    A host-to-device transfer costs by the call, not by the byte, so
    host leaves that share a placement share a transfer. The numpy
    leaves that are batch-sharded are grouped by (leading dim, item size
    of the dtype the device gives them). A group is packed into one
    ``(sum of trailing sizes, B)`` buffer of unsigned words, a row a
    leaf's column (the batch axis is the buffer's last and is sharded
    over the data axis as each leaf's was, so no sample crosses a
    device), and sent by one ``jax.device_put``; one jitted function
    (``_unpacker``, cached by the layout, so it compiles at the first
    batch of a shape) slices, transposes and bit-casts all groups back
    into leaves on the device.

    The call packs only where that makes fewer calls than it has leaves
    to pack: ``groups + 1 < leaves in them`` (26 id columns, a dense
    block and a label: 2 calls for 28; a dense block and a label: leaf
    by leaf). Every other leaf is one ``device_put`` as before: a
    ``jax.Array`` (never pulled back to the host), a bool or extension
    dtype (no bit-cast), a replicated or empty leaf, a leaf alone in its
    group. Either way the result is the same tree, and a host leaf comes
    back as a fresh device array of the dtype, shape, bits and sharding
    that ``jax.device_put(leaf, sharding)`` gives.

    Runs under a ``trainer/place_batch`` span: the host-to-device part
    of a step's host time. Its tags: ``leaves`` and ``bytes`` placed,
    ``transfers`` (the ``device_put`` calls made) and ``packed_leaves``
    (leaves that rode in a packed buffer; 0 where packing did not
    engage).
    """
    bsh = batch_sharding(mesh)
    rep = replicated(mesh)
    packed_sharding = NamedSharding(mesh, P(None, DATA_AXIS))
    data_size = mesh.shape[DATA_AXIS]

    with tracing.span("trainer/place_batch") as sp:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        groups = {}  # (leading dim, item size) -> [(leaf index, dtype)]
        for i, x in enumerate(leaves):
            if (isinstance(x, np.ndarray) and x.size
                    and _batch_sharded(x, data_size)):
                # what device_put makes of it: int64 is int32 with x64 off
                dtype = jax.dtypes.canonicalize_dtype(x.dtype)
                # what a bit-cast of unsigned words can give back
                if (dtype.kind in "iuf" and dtype.isnative
                        and dtype.itemsize in _WORD):
                    groups.setdefault((x.shape[0], dtype.itemsize),
                                      []).append((i, dtype))
        # a leaf alone in its group gains nothing from a buffer
        groups = {k: m for k, m in groups.items() if len(m) > 1}
        if len(groups) + 1 >= sum(map(len, groups.values())):
            groups = {}  # no fewer calls than leaf by leaf
        packed = [i for members in groups.values() for i, _ in members]

        riding = set(packed)
        placed = [
            x if i in riding else jax.device_put(
                x, bsh if _batch_sharded(x, data_size) else rep)
            for i, x in enumerate(leaves)]
        if groups:
            buffers, layout = [], []
            for (rows, itemsize), members in groups.items():
                # a leaf's columns, each a contiguous row of the buffer
                columns = [np.ascontiguousarray(
                    leaves[i].astype(dtype, copy=False).reshape(rows, -1).T)
                    for i, dtype in members]
                # bytes.join copies them in one call that keeps the
                # interpreter lock; np.concatenate gives it up once a
                # part, and under load every give-up is a hand-over
                buf = np.frombuffer(b"".join(columns), _WORD[itemsize])
                buffers.append(jax.device_put(
                    buf.reshape(-1, rows), packed_sharding))
                layout.append(tuple((leaves[i].shape, dtype)
                                    for i, dtype in members))
            for i, x in zip(packed, _unpacker(mesh, tuple(layout))(*buffers)):
                placed[i] = x
        if sp.ctx is not None:  # recording: count what was placed
            sp.tag(leaves=len(placed),
                   bytes=sum(x.nbytes for x in placed),
                   transfers=len(placed) - len(packed) + len(groups),
                   packed_leaves=len(packed))
    return jax.tree_util.tree_unflatten(treedef, placed)
