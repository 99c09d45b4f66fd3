"""Every cell's step, at its real sizes, compiled here for a described
v5e with no chip attached: what the chip's compiler would refuse is
refused here, and the bytes the compiler reckons have to fit the chip.
The topology is described inside a fixture (one process may hold libtpu;
see the on-chip-measurement guide, section 2)."""

import json
import os

import pytest

import manifest
import weights

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def cells_of(placement):
    man = manifest.Manifest(manifest.repo_root(BENCH_DIR))
    # the cell held back from the manifest keeps its files, and its step
    # is compiled here all the same
    with open(os.path.join(BENCH_DIR, "tests", "held_back.json")) as f:
        man.doc["workloads"] += json.load(f)["workloads"]
    out = []
    for w in man.doc["workloads"]:
        _, cell, config, _ = man.cell(w["name"])
        if cell["placement"] == placement:
            out.append(pytest.param(cell, config, id=w["name"]))
    return out


@pytest.mark.parametrize("cell,config", cells_of("device"))
def test_device_step_compiles_and_fits(topo, cell, config):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from persia_tpu.models import DLRM
    from persia_tpu.parallel.device_mode import (
        DeviceModeModel,
        make_device_mode_trainer,
    )

    sizes = cell["sizes"]
    shape = tuple(sizes["mesh"])
    mesh = Mesh(np.array(topo.devices[:shape[0] * shape[1]]).reshape(shape),
                ("data", "model"))
    rows = weights.table_rows(config, sizes.get("max_ind_range"),
                              multiple_of=shape[1])
    dim, batch = config["embedding_dim"], sizes["batch"]
    names = [f"C{t + 1}" for t in range(len(rows))]
    model = DeviceModeModel(
        slot_specs=[(n, r, dim) for n, r in zip(names, rows)],
        tower=DLRM(embedding_dim=dim,
                   bottom_mlp=tuple(config["bottom_mlp"][:-1]),
                   top_mlp=tuple(config["top_mlp"][:-1])))
    held = {}

    def build(non_id, ids):
        params, opt_state, held["step"] = make_device_mode_trainer(
            model, optax.adagrad(0.02), mesh, non_id, ids)
        return params, opt_state

    by_batch = NamedSharding(mesh, P("data"))
    non_id = [jax.ShapeDtypeStruct((batch, config["num_dense"]),
                                   jnp.float32, sharding=by_batch)]
    ids = {n: jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=by_batch)
           for n in names}
    label = jax.ShapeDtypeStruct((batch, 1), jnp.float32, sharding=by_batch)
    params, opt_state = jax.eval_shape(build, non_id, ids)

    def placed(tree):
        def one(x):
            spec = P("model", None) if x.ndim == 2 and x.shape[1] == dim \
                and x.shape[0] in rows else P()
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=NamedSharding(mesh, spec))
        return jax.tree_util.tree_map(one, tree)

    with mesh:
        compiled = held["step"].lower(placed(params), placed(opt_state),
                                      non_id, ids, label).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({"cell": cell["name"], "rows": sum(rows),
                      "argument_bytes": mem.argument_size_in_bytes,
                      "temp_bytes": mem.temp_size_in_bytes,
                      "total_bytes": total}))
    assert total < 0.9 * HBM, total
    # the cell has to stand for a deployment: a quarter of the chip or more
    assert mem.argument_size_in_bytes > 0.25 * HBM


@pytest.mark.parametrize("cell,config", cells_of("cached"))
@pytest.mark.parametrize("misses", [16384, 65536])
def test_cached_step_compiles_and_fits(topo, cell, config, misses):
    """The cached cell's fused step at its cache size, for the two padded
    miss buckets its traffic lands in."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import SingleDeviceSharding

    from persia_tpu.models import DLRM
    from persia_tpu.parallel.cached_train import make_cached_train_step
    from persia_tpu.parallel.train import create_train_state

    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    sizes, opt = cell["sizes"], cell["row_optimizer"]
    dim, batch = config["embedding_dim"], sizes["batch"]
    slots, rows = len(config["table_cardinalities"]), sizes["cache_rows"] + 1
    model = DLRM(embedding_dim=dim,
                 bottom_mlp=tuple(config["bottom_mlp"][:-1]),
                 top_mlp=tuple(config["top_mlp"][:-1]))
    optimizer = optax.adagrad(0.02)
    non_id = [sds((batch, config["num_dense"]))]
    state = jax.eval_shape(
        lambda n, e: create_train_state(model, optimizer,
                                        jax.random.key(0), n, e),
        non_id, [sds((batch, dim))] * slots)
    state = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), state)
    step = make_cached_train_step(
        model, optimizer, slots, dim, lr=opt["lr"], eps=opt["eps"],
        g_square_momentum=opt["g_square_momentum"],
        weight_bound=opt["weight_bound"], capacity=sizes["cache_rows"])
    compiled = step.lower(
        state, sds((rows, dim)), sds((rows, dim)), non_id,
        sds((batch, slots), jnp.int32), sds((misses,), jnp.int32),
        sds((misses, dim)), sds((misses, dim)),
        sds((batch * slots,), jnp.int32), sds((batch * slots,), jnp.int32),
        sds((batch, 1))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({"cell": cell["name"], "misses": misses,
                      "argument_bytes": mem.argument_size_in_bytes,
                      "temp_bytes": mem.temp_size_in_bytes,
                      "total_bytes": total}))
    assert total < 0.9 * HBM, total
    assert mem.argument_size_in_bytes > 0.25 * HBM
