"""The ``device_seq_window`` placement's step, at its cell's real sizes,
compiled here for a described v5e with no chip attached: what the chip's
compiler would refuse (the flash kernels over the band's block pairs in
six window layers and over the causal ones in two full layers, forward
and backward under ``nn.remat``; the grouped product over eight held
experts under a softmax router) is refused here, and the bytes the
compiler reckons have to fit the chip and pass a quarter of it; the
configuration's ``parameters_as_run`` is met leaf for leaf. The topology
is described inside a fixture (one process may hold libtpu)."""

import json
import os
import re

import pytest

import manifest

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
    out = []
    for w in man.doc["workloads"]:
        _, cell, config, mix_file = man.cell(w["name"])
        if cell["placement"] == placement:
            with open(mix_file) as f:
                out.append(pytest.param(cell, config, json.load(f),
                                        id=w["name"]))
    return out


def custom_calls(text, scope):
    """The kernel calls of the compiled text whose op name runs through
    ``scope``."""
    return sum(1 for line in text.splitlines()
               if "tpu_custom_call" in line
               and re.search(rf'op_name="[^"]*/{scope}/', line))


@pytest.mark.parametrize("cell,config,mix", cells_of("device_seq_window"))
def test_device_seq_window_step_compiles_and_fits(topo, cell, config, mix,
                                                  monkeypatch):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import weights_window_seq as weights
    from placements import device_seq_window as placement
    from persia_tpu.parallel.device_mode import make_device_mode_trainer
    from persia_tpu.parallel.train import next_item_cross_entropy

    sizes = cell["sizes"]
    shape = tuple(sizes["mesh"])
    mesh = Mesh(np.array(topo.devices[:shape[0] * shape[1]]).reshape(shape),
                ("data", "model"))
    sz = weights.sizes_of(config)
    length = min(mix["session_length"], sizes["batch"])
    histories = sizes["batch"] // length
    model = placement.build_model(sz)
    held = {}

    def build(ids):
        params, opt_state, held["step"] = make_device_mode_trainer(
            model, placement.build_optimizer(config["optimizer"]), mesh,
            [], ids, loss_fn=next_item_cross_entropy)
        return params, opt_state

    by_batch = NamedSharding(mesh, P("data"))
    short = {placement.SLOT: jax.ShapeDtypeStruct(
        (histories, 128), jnp.int32, sharding=by_batch)}
    ids = {placement.SLOT: jax.ShapeDtypeStruct(
        (histories, length), jnp.int32, sharding=by_batch)}
    label = jax.ShapeDtypeStruct((histories, length), jnp.int32,
                                 sharding=by_batch)
    params, opt_state = jax.eval_shape(build, short)
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        params)) == config["parameters_as_run"]
    # leaf for leaf: the program's tree against the configuration's specs
    from tree_paths import get
    paths = placement.leaf_paths(sz)
    specs = weights.leaf_specs(sz)
    assert len(jax.tree_util.tree_leaves(params)) == len(specs)
    for name, shape, _ in specs:
        assert tuple(get(params, paths[name]).shape) == tuple(shape), name
    table = (sz["vocab"], sz["hidden"])

    def placed(tree):
        def one(x):
            spec = P("model", None) if x.shape == table else P()
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=NamedSharding(mesh, spec))
        return jax.tree_util.tree_map(one, tree)

    # the Pallas entries ask the default backend whether to compile or to
    # interpret, and here that is the CPU: steer them to the kernels the
    # chip runs, so that Mosaic's refusals are refused here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with mesh:
        compiled = held["step"].lower(placed(params), placed(opt_state), [],
                                      ids, label).compile()
    text = compiled.as_text()
    # three flash calls (forward with lse, dq, dk/dv) in each attention
    # layer's step: six window layers' under `window_attention`, two full
    # layers' under `flash_attention`; the forward is not run again where
    # `nn.remat` rebuilds a layer (its `out` and `lse` are kept)
    calls = {scope: custom_calls(text, scope)
             for scope in ("window_attention", "flash_attention")}
    assert calls == {"window_attention": 3 * 6, "flash_attention": 3 * 2}
    # and the grouped product's calls in eight expert layers' loops
    assert text.count("tpu_custom_call") >= 3 * 8 + 8
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({"cell": cell["name"],
                      "code_bytes": mem.generated_code_size_in_bytes,
                      "custom_calls": text.count("tpu_custom_call"),
                      "by_scope": calls,
                      "argument_bytes": mem.argument_size_in_bytes,
                      "temp_bytes": mem.temp_size_in_bytes,
                      "total_bytes": total}))
    # the chip's allocator has 16.9 GB (bytes_limit); the compiler's own
    # count leaves out what the process holds besides one program
    assert total < 15.5e9, total
    # the cell has to stand for a deployment: a quarter of the chip or more
    assert mem.argument_size_in_bytes > 0.25 * HBM
