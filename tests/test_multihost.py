"""Multi-host dense path: 2-process ``jax.distributed`` rendezvous on CPU.

The reference's nn-workers rendezvous through NATS master discovery and
then run NCCL process-group collectives (persia-core/src/nats.rs:22-100,
persia/distributed.py:174-193). Here ``DistributedOption(multihost=True)``
wraps ``jax.distributed.initialize``; this test spawns two real processes
against one coordinator and runs a cross-process collective + a pjit'd
global-mesh reduction, proving the path works end-to-end without TPU
hardware (same cluster-in-a-box pattern as SURVEY.md §4)."""

import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# jax.distributed.initialize must be the FIRST backend init in the
# worker, on the CPU platform.
_WORKER_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
}

_WORKER = r"""
import sys

sys.path.insert(0, "@REPO@")
from persia_tpu.utils import force_cpu_platform

# verify=False: jax.distributed.initialize must be the first backend init
force_cpu_platform(1, verify=False)

import jax
import jax.numpy as jnp

from persia_tpu.distributed import DistributedOption

pid = int(sys.argv[1])
opt = DistributedOption(
    multihost=True,
    coordinator_address="127.0.0.1:" + sys.argv[2],
    num_processes=2,
    process_id=pid,
)
mesh = opt.initialize()
assert jax.process_count() == 2, jax.process_count()
n_local = jax.local_device_count()
n_total = len(jax.devices())  # global view spans both processes
assert n_total == 2 * n_local, (n_total, n_local)

# cross-process collective: gather each process's contribution
from jax.experimental import multihost_utils

gathered = multihost_utils.process_allgather(jnp.array([float(pid + 1)]))
total = float(gathered.sum())
assert total == 3.0, total

# pjit over the global mesh: data-parallel mean of a process-sharded array
from jax.sharding import NamedSharding, PartitionSpec as P

global_shape = (n_total, 8)
sharding = NamedSharding(mesh, P("data", None))
local = jnp.full((n_local, 8), float(pid + 1))
arr = jax.make_array_from_process_local_data(sharding, local, global_shape)
mean = jax.jit(lambda x: x.mean(), out_shardings=None)(arr)
assert abs(float(mean) - 1.5) < 1e-6, float(mean)

# int8_ef compressed reduction across REAL processes: the ef_state is
# data-axis-sharded over a mesh spanning both hosts (the mode's stated
# target), and the two-phase all_to_all/all_gather rides the
# cross-process backend
import numpy as np
import optax

from persia_tpu.models import DNN
from persia_tpu.parallel.train import (
    create_train_state,
    init_ef_state,
    make_packed_train_step_ddp,
)

rng = np.random.default_rng(0)  # same on both processes -> same init
# global batch must divide by the data axis (= all devices, both hosts)
bs_local, slot_dims = 2 * n_local, [8, 8]
non_id_l = rng.normal(size=(bs_local, 5)).astype(np.float32)
emb_l = rng.normal(size=(bs_local, 16)).astype(np.float32)
label_l = rng.integers(0, 2, size=(bs_local, 1)).astype(np.float32)
model = DNN()
opt2 = optax.sgd(0.1)
state = create_train_state(
    model, opt2, jax.random.key(0),
    [jnp.zeros((2 * bs_local, 5))],
    [jnp.zeros((2 * bs_local, 8)), jnp.zeros((2 * bs_local, 8))])
step = make_packed_train_step_ddp(model, opt2, slot_dims, mesh,
                                  grad_reduce_dtype="int8_ef")
ef = init_ef_state(state.params, mesh)
assert not ef.is_fully_addressable  # really spans both processes

def shard2(local, width):
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")), local, (2 * bs_local, width))

flat_emb = shard2(jnp.asarray(emb_l, jnp.bfloat16), 16)
loss = None
for _ in range(2):  # second step consumes the carried residual
    state, loss, flat_grads, pred, ef = step(
        state, [shard2(non_id_l, 5)], flat_emb, shard2(label_l, 1), ef)
loss = float(loss)
assert loss == loss, "int8_ef loss is NaN"
print(f"proc {pid} ok total={total} mean={float(mean)} ef_loss={loss:.4f}")
"""


def test_two_process_distributed_rendezvous_and_collective():
    from persia_tpu.utils import find_free_port

    port = find_free_port()
    script = _WORKER.replace("@REPO@", str(REPO_ROOT))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_WORKER_ENV,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid} ok" in out
