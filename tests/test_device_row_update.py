"""Device mode's touched-rows step: equal to the whole-table step wherever
it is taken, taken only where it is equal, and free of whole-table work."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from persia_tpu import metrics, tracing
from persia_tpu.models import DLRM
from persia_tpu.parallel.device_embedding import distinct_rows, rows_suffice
from persia_tpu.parallel.device_mode import (
    DeviceModeModel,
    make_device_mode_trainer,
)
from persia_tpu.parallel.mesh import make_mesh, shard_batch_pytree
from persia_tpu.parallel.train import bce_loss

BATCH, DENSE, DIM = 32, 13, 8
SPECS = [("a", 64, DIM), ("b", 128, DIM), ("c", 32, DIM)]


def _gauges():
    reg = metrics.default_registry()
    return tuple(int(reg.gauge(f"device_mode_{n}_update_tables").value)
                 for n in ("row", "dense"))


def _batch(seed, sfs, kind):
    rng = np.random.default_rng(seed)
    if kind == "duplicates":    # a handful of hot ids, many repeats
        ids = {n: rng.choice(rng.integers(1, 1 << 31, size=5),
                             size=(BATCH, sfs)) for n, _, _ in SPECS}
    else:                       # padding: a third of the positions are id 0
        ids = {n: rng.integers(1, 1 << 31, size=(BATCH, sfs))
               * (rng.random((BATCH, sfs)) > 0.33) for n, _, _ in SPECS}
    return ([rng.normal(size=(BATCH, DENSE)).astype(np.float32)],
            {n: v.astype(np.int32) for n, v in ids.items()},
            rng.integers(0, 2, size=(BATCH, 1)).astype(np.float32))


def _dense_step(model, optimizer):
    """The whole-table step, kept here as the reference: autodiff over
    the whole tree, the optimizer over every table."""
    def step(params, opt_state, non_id, ids, label):
        def compute_loss(params):
            return bce_loss(model.apply({"params": params}, non_id, ids,
                                        train=True), label)

        loss, grads = jax.value_and_grad(compute_loss)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(step)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 4)],
                         ids=["one_device", "mesh_2x4"])
@pytest.mark.parametrize("sfs,pooling", [(1, "sum"), (4, "mean")],
                         ids=["one_id", "bag4_mean"])
@pytest.mark.parametrize("kind", ["duplicates", "padding"])
def test_row_step_equals_the_dense_step(kind, sfs, pooling, mesh_shape):
    n = mesh_shape[0] * mesh_shape[1]
    mesh = make_mesh(mesh_shape, devices=jax.devices()[:n])
    model = DeviceModeModel(slot_specs=SPECS, tower=DLRM(embedding_dim=DIM),
                            pooling=pooling)
    optimizer = optax.adagrad(0.05, initial_accumulator_value=0.1, eps=1e-7)
    non_id, ids, _ = _batch(0, sfs, kind)
    params, opt_state, step = make_device_mode_trainer(
        model, optimizer, mesh, non_id, ids)
    assert _gauges() == (len(SPECS), 0)
    dense_step = _dense_step(model, optimizer)
    ref = jax.tree.map(jnp.copy, (params, opt_state))   # step donates its own
    with mesh:
        for k in range(5):
            feed = shard_batch_pytree(
                dict(zip("nil", _batch(k, sfs, kind))), mesh)
            feed = (feed["n"], feed["i"], feed["l"])
            params, opt_state, loss = step(params, opt_state, *feed)
            *ref, ref_loss = dense_step(*ref, *feed)
            np.testing.assert_allclose(float(loss), float(ref_loss),
                                       rtol=1e-6)
    # parameters, and every state leaf (the table-shaped accumulators
    # among them), after five steps
    got = jax.tree.leaves((params, opt_state))
    want = jax.tree.leaves(tuple(ref))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    # the tables moved, and only where a batch touched them
    table = np.asarray(params["DeviceEmbeddingCollection_0"]["bag_b"]["table"])
    acc = np.asarray(opt_state[0].sum_of_squares[
        "DeviceEmbeddingCollection_0"]["bag_b"]["table"])
    moved = (acc != np.float32(0.1)).any(axis=1)
    assert 0 < moved.sum() < len(table) and not moved[0]


OPTIMIZERS = {
    "adagrad": (lambda: optax.adagrad(0.05), True),
    "sgd": (lambda: optax.sgd(0.1), True),
    "adam": (lambda: optax.adam(1e-3), False),
    "sgd_momentum": (lambda: optax.sgd(0.1, momentum=0.9), False),
    "adagrad_decayed_weights": (lambda: optax.chain(
        optax.add_decayed_weights(1e-4), optax.adagrad(0.05)), False),
    # zero gradient is a fixed point, but a row's step leans on the leaf
    "sgd_trust_ratio": (lambda: optax.chain(
        optax.scale_by_trust_ratio(), optax.sgd(0.1)), False),
}


@pytest.mark.parametrize("name", list(OPTIMIZERS))
def test_the_optimizer_decides_the_path(name):
    make, by_row = OPTIMIZERS[name]
    assert rows_suffice(make()) is by_row
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    model = DeviceModeModel(slot_specs=SPECS, tower=DLRM(embedding_dim=DIM))
    non_id, ids, label = _batch(0, 2, "duplicates")
    tracing.enable_tracing(True)
    try:
        params, opt_state, step = make_device_mode_trainer(
            model, make(), mesh, non_id, ids)
        built = [s for s in tracing.default_collector().recent()
                 if s.name == "trainer/build_device_step"][-1]
    finally:
        tracing.enable_tracing(False)
    want = (len(SPECS), 0) if by_row else (0, len(SPECS))
    assert _gauges() == want
    assert (built.tags["row_update_tables"],
            built.tags["dense_update_tables"]) == want
    # the state's layout is the caller's optimizer's, whichever path
    assert (jax.tree.structure(opt_state)
            == jax.tree.structure(make().init(params)))
    with mesh:
        lowered = step.lower(params, opt_state, non_id, ids, label)
        params, opt_state, loss = step(params, opt_state, non_id, ids, label)
    assert np.isfinite(float(loss))
    text = lowered.as_text(debug_info=True)
    assert ("optimizer/row_update/" in text) is by_row


def _results_of_type(lowered, tensor_type):
    """Names of the lowered module's operations with a result of that
    type (the operations of ``lowered.as_text()``, walked, not parsed)."""
    from jaxlib.mlir import ir

    found = []

    def visit(op):
        found.extend(op.name for r in op.results
                     if str(r.type) == tensor_type)
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir("stablehlo").operation.walk(visit)
    return found


@pytest.mark.parametrize("name,state_tables", [("adagrad", 1), ("sgd", 0)])
def test_built_under_eval_shape_the_row_step_has_no_whole_table_result(
        name, state_tables):
    """As ``benchmarks/chip/tests/test_compile_v5e.py`` builds it: inside
    ``jax.eval_shape`` the probe still answers, and nothing in the step
    has a whole table for a result but the scatters that write the rows
    (and, for Adagrad, the accumulator's rows) back."""
    specs = [(n, 96, DIM) for n, _, _ in SPECS]     # a shape nothing shares
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    model = DeviceModeModel(slot_specs=specs, tower=DLRM(embedding_dim=DIM))
    non_id, ids, label = _batch(0, 2, "duplicates")
    held = {}

    def build(non_id, ids):
        params, opt_state, held["step"] = make_device_mode_trainer(
            model, OPTIMIZERS[name][0](), mesh, non_id, ids)
        return params, opt_state

    params, opt_state = jax.eval_shape(build, non_id, ids)
    assert _gauges() == (len(specs), 0)
    with mesh:
        lowered = held["step"].lower(params, opt_state, non_id, ids, label)
    whole = _results_of_type(lowered, f"tensor<96x{DIM}xf32>")
    assert whole == ["stablehlo.scatter"] * (len(specs) * (1 + state_tables))
    # and the dense step, for what the check would have seen before
    held.clear()

    def build_dense(non_id, ids):
        params, opt_state, held["step"] = make_device_mode_trainer(
            model, optax.adam(1e-3), mesh, non_id, ids)
        return params, opt_state

    params, opt_state = jax.eval_shape(build_dense, non_id, ids)
    with mesh:
        lowered = held["step"].lower(params, opt_state, non_id, ids, label)
    assert set(_results_of_type(lowered, f"tensor<96x{DIM}xf32>")) - {
        "stablehlo.scatter"}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_distinct_rows_are_sorted_unique_and_padded_out_of_range(seed):
    rng = np.random.default_rng(seed)
    index = rng.integers(0, 12, size=(3, 40)).astype(np.int32)
    index[1] = 7                                    # one row, all equal
    beyond = np.array([12, 50, 1000], np.int32)
    touched, slot = map(np.asarray, jax.jit(distinct_rows)(index, beyond))
    for t in range(3):
        distinct = np.unique(index[t])
        assert (np.diff(touched[t]) > 0).all()      # the scatter's promise
        np.testing.assert_array_equal(touched[t][:len(distinct)], distinct)
        assert (touched[t][len(distinct):] >= beyond[t]).all()
        np.testing.assert_array_equal(touched[t][slot[t]], index[t])
