"""Device-mesh helpers: the TPU-native replacement for the reference's
DDP/NCCL process groups (persia/distributed.py:74-201).

A PERSIA-style job maps onto a 2-D mesh:

- ``data`` axis — synchronous data parallelism of the dense tower (the
  reference's DDP allreduce becomes an XLA psum over ICI)
- ``model`` axis — sharding of device-resident embedding tables (the
  TPU-first alternative to CPU parameter servers; CPU-PS mode uses a
  1-D data mesh)
"""

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
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


def shard_batch_pytree(tree, mesh: Mesh):
    """device_put every array leaf with its batch dim over the data axis.

    Leaves whose leading dim does not divide the data-axis size are
    replicated instead — notably raw-slot distinct-embedding tensors of
    capacity batch*sample_fixed_size+1, which are indexed globally and
    must be visible to every data shard. Scalars are replicated.

    Runs under a ``trainer/place_batch`` span: the host-to-device part
    of a step's host time.
    """
    bsh = batch_sharding(mesh)
    rep = replicated(mesh)
    data_size = mesh.shape[DATA_AXIS]

    def place(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] % data_size == 0:
            return jax.device_put(x, bsh)
        return jax.device_put(x, rep)

    with tracing.span("trainer/place_batch") as sp:
        placed = jax.tree_util.tree_map(place, tree)
        if sp.ctx is not None:  # recording: count what was placed
            leaves = jax.tree_util.tree_leaves(placed)
            sp.tag(leaves=len(leaves),
                   bytes=sum(x.nbytes for x in leaves))
    return placed
