"""Sanity-probe the device-mode step time claimed by bench.py.

Questions this answers on the real chip:
  1. per-step time with a hard sync every step (no async pipelining
     flattering the loop timing) vs the bench's end-sync loop;
  2. vocab scaling: if step time grows ~linearly with vocab the
     embedding update is dense (scatter -> dense adagrad); ~flat is what
     the touched-rows step (device_mode.py, taken for Adagrad and plain
     SGD) should read;
  3. fixed vs fresh ids per step (rules out cross-dispatch caching).
"""

import time

import jax
import numpy as np
import optax

import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from persia_tpu.utils import arm_watchdog

# chip-touching tool: in-process watchdog
# (a hung backend call must end the probe, not hang it)
arm_watchdog(1200, label=__file__)

from persia_tpu.models import DLRM
from persia_tpu.parallel.device_mode import (
    DeviceModeModel,
    criteo_like_specs,
    make_device_mode_trainer,
    synthetic_device_batch,
)
from persia_tpu.parallel.mesh import make_mesh

BS = 4096
NUM_DENSE = 13
NUM_SLOTS = 26
DIM = 16


def run(vocab, steps=30, fresh_ids=False):
    devices = jax.devices()
    mesh = make_mesh((len(devices), 1), devices=devices)
    specs = criteo_like_specs(num_slots=NUM_SLOTS, vocab=vocab, dim=DIM)
    model = DeviceModeModel(slot_specs=specs, tower=DLRM(embedding_dim=DIM))
    non_id, ids, label = synthetic_device_batch(BS, NUM_DENSE, specs)
    opt = optax.adagrad(0.02)
    params, opt_state, step = make_device_mode_trainer(
        model, opt, mesh, non_id, ids)
    rng = np.random.default_rng(1)
    id_variants = []
    if fresh_ids:
        for _ in range(4):
            id_variants.append({
                name: jax.device_put(jax.numpy.asarray(
                    rng.integers(1, 1 << 31, size=(BS, 1)), jax.numpy.int32))
                for name, _, _ in specs})
    with mesh:
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, non_id, ids,
                                           label)
        jax.block_until_ready(loss)
        # end-sync loop (what bench.py times)
        t0 = time.perf_counter()
        for i in range(steps):
            use = id_variants[i % 4] if fresh_ids else ids
            params, opt_state, loss = step(params, opt_state, non_id, use,
                                           label)
        jax.block_until_ready(loss)
        end_sync = (time.perf_counter() - t0) / steps
        # hard per-step sync
        t0 = time.perf_counter()
        for i in range(steps):
            use = id_variants[i % 4] if fresh_ids else ids
            params, opt_state, loss = step(params, opt_state, non_id, use,
                                           label)
            jax.block_until_ready(loss)
        per_sync = (time.perf_counter() - t0) / steps
    return end_sync, per_sync


def main():
    print("platform:", jax.devices()[0].platform)
    for vocab, tag in ((1 << 16, "2^16"), (1 << 18, "2^18"),
                       (1 << 20, "2^20")):
        es, ps = run(vocab)
        print(f"vocab {tag}: end-sync {es*1e3:.3f} ms/step, "
              f"per-step-sync {ps*1e3:.3f} ms/step, "
              f"samples/s (per-sync) {BS/ps:,.0f}")
    es, ps = run(1 << 20, fresh_ids=True)
    print(f"vocab 2^20 fresh-ids: end-sync {es*1e3:.3f} per-sync "
          f"{ps*1e3:.3f} ms/step, samples/s {BS/ps:,.0f}")


if __name__ == "__main__":
    main()
