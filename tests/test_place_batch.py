"""``shard_batch_pytree`` packs the host leaves that share a placement into
one transfer and unpacks them on the device: the tree it returns is, leaf
by leaf, what one ``jax.device_put`` a leaf returns (kept here as the
reference), it packs only where that makes fewer calls, and it compiles
once a batch signature."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from persia_tpu import tracing
from persia_tpu.models import DLRM
from persia_tpu.parallel import mesh as mesh_module
from persia_tpu.parallel.device_mode import (
    DeviceModeModel,
    make_device_mode_trainer,
)
from persia_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    make_mesh,
    replicated,
    shard_batch_pytree,
)

MESHES = [(1, 1), (8, 1), (2, 2)]
MESH_IDS = ["one_device", "mesh_8x1", "mesh_2x2"]


def _mesh(shape):
    return make_mesh(shape, devices=jax.devices()[:shape[0] * shape[1]])


def _placement(x, mesh):
    data_size = mesh.shape[DATA_AXIS]
    if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] % data_size == 0:
        return batch_sharding(mesh)
    return replicated(mesh)


def _leaf_by_leaf(tree, mesh):
    """The placement as it was before packing: one transfer a leaf."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, _placement(x, mesh)), tree)


def _bits(x):
    """The leaf's bytes, so that NaN payloads and -0.0 compare."""
    x = np.ascontiguousarray(np.asarray(x))
    return x.reshape(-1).view(np.uint8)


def _assert_same_tree(got, want):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        where = jax.tree_util.keystr(path)
        assert isinstance(a, jax.Array), where
        assert a.dtype == b.dtype and a.shape == b.shape, where
        assert a.sharding == b.sharding, where
        np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=where)


def _dlrm_tree(rows, seed=0):
    rng = np.random.default_rng(seed)
    return {"n": [rng.normal(size=(rows, 13)).astype(np.float32)],
            "i": {f"slot_{t}": rng.integers(0, 1 << 31, size=(rows, 1))
                  .astype(np.int32) for t in range(26)},
            "l": rng.integers(0, 2, size=(rows, 1)).astype(np.float32)}


@pytest.fixture
def place_spans():
    """Tags of the ``trainer/place_batch`` spans the test's calls made."""
    ring = tracing.default_collector()
    ring.clear()
    tracing.enable_tracing(True)
    try:
        yield lambda: [s.tags for s in ring.recent()
                       if s.name == "trainer/place_batch"]
    finally:
        tracing.enable_tracing(False)
        ring.clear()


@pytest.fixture
def device_puts(monkeypatch):
    """What the calls of ``jax.device_put`` were given, in order."""
    seen, real = [], jax.device_put

    def counting(x, *args, **kwargs):
        seen.append(x)
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting)
    return seen


@pytest.fixture
def compilations():
    """Counts JAX's backend compilations (cache loads included)."""
    count = [0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield lambda: count[0]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


# --- (a) the DLRM cells' tree --------------------------------------------


@pytest.mark.parametrize("shape", MESHES, ids=MESH_IDS)
def test_dlrm_tree_equals_leaf_by_leaf(shape, place_spans, device_puts):
    mesh = _mesh(shape)
    tree = _dlrm_tree(64)
    got = shard_batch_pytree(tree, mesh)
    assert len(device_puts) == 1 and device_puts[0].shape == (13 + 27, 64)
    _assert_same_tree(got, _leaf_by_leaf(tree, mesh))
    assert place_spans() == [{"leaves": 28, "bytes": 64 * 40 * 4,
                              "transfers": 1, "packed_leaves": 28}]


# --- (b) a mixed tree -----------------------------------------------------


@pytest.mark.parametrize("shape", MESHES, ids=MESH_IDS)
def test_mixed_tree_equals_leaf_by_leaf(shape, place_spans, device_puts):
    mesh = _mesh(shape)
    rng = np.random.default_rng(1)
    odd = np.float32([np.nan, -np.nan, -0.0, 0.0, np.inf, -np.inf, 1e-45, 1.0])
    payloads = np.tile(odd, 8).reshape(64, 1)
    # quiet and signalling NaNs with payloads, either sign
    payloads.view(np.uint32)[:4, 0] = [0x7FC12345, 0xFFC00001,
                                       0x7F800001, 0xFFBFFFFF]
    on_device = jax.device_put(
        rng.integers(0, 99, size=(64, 2)).astype(np.int32),
        batch_sharding(mesh))
    tree = {
        "f32": payloads,
        "f32_wide": rng.normal(size=(64, 3, 5)).astype(np.float32),
        "i32": rng.integers(-9, 9, size=(64, 2)).astype(np.int32),
        "u32_flat": rng.integers(0, 1 << 32, size=(64,), dtype=np.uint32),
        "i64": rng.integers(-(1 << 40), 1 << 40, size=(64, 1)),
        "f64": rng.normal(size=(64, 2)),
        "f16": rng.normal(size=(64, 3)).astype(np.float16),
        "i16": rng.integers(-99, 99, size=(64, 1)).astype(np.int16),
        "u8": rng.integers(0, 255, size=(64, 4)).astype(np.uint8),
        "i8": rng.integers(-99, 99, size=(64, 1)).astype(np.int8),
        "bf16": rng.normal(size=(64, 2)).astype(jnp.bfloat16),
        "bool": rng.random((64, 1)) > 0.5,
        "columns": np.asfortranarray(
            rng.normal(size=(64, 4)).astype(np.float32))[:, 1:3],
        "other_rows": rng.normal(size=(128, 2)).astype(np.float32),
        "other_rows_ids": rng.integers(0, 9, size=(128, 1)).astype(np.int32),
        "raw_slot": rng.normal(size=(65, 4)).astype(np.float32),
        "empty": np.zeros((64, 0), np.float32),
        "scalar": np.float32(2.5),
        "number": 3,
        "on_device": on_device,
        "nothing": None,
    }
    assert tree["i64"].dtype == np.int64 and tree["f64"].dtype == np.float64
    del device_puts[:]
    got = shard_batch_pytree(tree, mesh)
    made = len(device_puts)
    _assert_same_tree(got, _leaf_by_leaf(tree, mesh))
    assert got["on_device"] is on_device    # no host round trip
    assert got["i64"].dtype == jnp.int32 and got["f64"].dtype == jnp.float32
    if shape != (1, 1):     # 65 rows do not divide the data axis
        assert got["raw_slot"].sharding == replicated(mesh)
    # rode together: by (rows, item size) the four-byte leaves of 64 rows,
    # the two-byte ones, the one-byte ones, the four-byte leaves of 128
    # rows; alone: bfloat16 and bool (no bit-cast), the empty leaf, the
    # scalars, the device array, and the 65 rows (replicated on a mesh,
    # the only leaf of their group on one device)
    (tags,) = place_spans()
    assert tags["leaves"] == 20 and tags["packed_leaves"] == 7 + 2 + 2 + 2
    assert tags["transfers"] == made == 20 - 13 + 4


def test_a_leaf_device_put_refuses_is_still_refused():
    """A byte-swapped leaf rides in no buffer: it goes alone and raises
    what ``jax.device_put`` raises."""
    mesh = _mesh((2, 2))
    tree = [np.arange(8, dtype=np.int32).reshape(8, 1) for _ in range(3)]
    tree.append(np.arange(8, dtype=">i4").reshape(8, 1))
    with pytest.raises(TypeError, match="not a valid JAX array type"):
        shard_batch_pytree(tree, mesh)


# --- (c) when packing engages ---------------------------------------------


@pytest.mark.parametrize("leaves,transfers,packed", [
    (1, 1, 0), (2, 2, 0), (3, 1, 3), (4, 1, 4)])
def test_packs_only_where_it_makes_fewer_calls(
        leaves, transfers, packed, place_spans, device_puts):
    mesh = _mesh((8, 1))
    rng = np.random.default_rng(leaves)
    tree = [rng.integers(0, 99, size=(16, 8 + k)).astype(np.int32)
            for k in range(leaves)]
    built = mesh_module._unpacker.cache_info().misses
    got = shard_batch_pytree(tree, mesh)
    assert len(device_puts) == transfers
    _assert_same_tree(got, _leaf_by_leaf(tree, mesh))
    assert place_spans() == [{
        "leaves": leaves, "bytes": sum(x.nbytes for x in tree),
        "transfers": transfers, "packed_leaves": packed}]
    # an unpack function exists only where the call packed
    assert (mesh_module._unpacker.cache_info().misses - built) == (packed > 0)


def test_every_group_counts_against_the_leaves(place_spans):
    """Two item sizes of two leaves each: 2 transfers and 1 unpack are
    fewer than 4, so they pack; with one leaf less they are not fewer
    than 3, so each leaf goes alone."""
    mesh = _mesh((2, 2))
    rng = np.random.default_rng(7)
    four = [rng.normal(size=(8, 3)).astype(np.float32) for _ in range(2)]
    two = [rng.normal(size=(8, 3)).astype(np.float16) for _ in range(2)]
    for tree, transfers, packed in ((four + two, 2, 4), (four + two[:1], 3, 0)):
        _assert_same_tree(shard_batch_pytree(tree, mesh),
                          _leaf_by_leaf(tree, mesh))
        tags = place_spans()[-1]
        assert (tags["transfers"], tags["packed_leaves"]) == (transfers, packed)


# --- (d) one compilation a signature ---------------------------------------


def test_a_signature_compiles_once(compilations):
    mesh = _mesh((2, 2))
    # 52 and 76 rows: batch sizes no other test of this process places
    tree = _dlrm_tree(52, seed=1)
    _assert_same_tree(shard_batch_pytree(tree, mesh), _leaf_by_leaf(tree, mesh))
    first = compilations()
    for seed in (2, 3):
        tree = _dlrm_tree(52, seed=seed)
        _assert_same_tree(shard_batch_pytree(tree, mesh),
                          _leaf_by_leaf(tree, mesh))
    assert compilations() == first
    tree = _dlrm_tree(76, seed=4)
    got = shard_batch_pytree(tree, mesh)
    assert compilations() == first + 1
    _assert_same_tree(got, _leaf_by_leaf(tree, mesh))
    shard_batch_pytree(_dlrm_tree(52, seed=5), mesh)
    assert compilations() == first + 1


# --- (e) the step sees the same bits ---------------------------------------

BATCH, DIM = 32, 8
SPECS = [("a", 64, DIM), ("b", 128, DIM), ("c", 32, DIM)]


def _batch(seed):
    rng = np.random.default_rng(seed)
    return {"n": [rng.normal(size=(BATCH, 13)).astype(np.float32)],
            "i": {n: rng.integers(1, 1 << 31, size=(BATCH, 2)).astype(np.int32)
                  for n, _, _ in SPECS},
            "l": rng.integers(0, 2, size=(BATCH, 1)).astype(np.float32)}


@pytest.mark.parametrize("shape", [(1, 1), (2, 4)],
                         ids=["one_device", "mesh_2x4"])
def test_five_steps_fed_packed_equal_five_fed_leaf_by_leaf(shape, place_spans):
    mesh = _mesh(shape)
    model = DeviceModeModel(slot_specs=SPECS, tower=DLRM(embedding_dim=DIM))
    ends = []
    for place in (shard_batch_pytree, _leaf_by_leaf):
        params, opt_state, step = make_device_mode_trainer(
            model, optax.adagrad(0.05), mesh, _batch(0)["n"], _batch(0)["i"],
            seed=3)
        losses = []
        with mesh:
            for k in range(5):
                feed = place(_batch(k), mesh)
                params, opt_state, loss = step(
                    params, opt_state, feed["n"], feed["i"], feed["l"])
                losses.append(loss)
        ends.append(jax.tree.leaves((losses, params, opt_state)))
    assert all(t["packed_leaves"] == 5 and t["transfers"] == 1
               for t in place_spans()) and len(place_spans()) == 5
    assert len(ends[0]) == len(ends[1])
    for a, b in zip(*ends):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
