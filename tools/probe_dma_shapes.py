"""Probe which per-row DMA shapes Mosaic accepts on the attached TPU.

Round-4 kernel work: the original embedding-bag kernel per-row-DMA'd
(dim,)-shaped rows (dim=16) out of an HBM table and real Mosaic rejected
the sub-(8,128) copy (interpret mode had hidden it). The lane-packed
redesign needs to know exactly which copy shapes are legal:

  A. (16,)   — raw sub-lane row           (expected: reject)
  B. (128,)  — one full lane row, 1-D     (the lane-packed bet)
  C. (1,128) — one full lane row, 2-D
  D. (8,128) — one full f32 tile          (expected: accept)

Run on real TPU only (CPU interpret mode accepts everything).
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from persia_tpu.utils import arm_watchdog

# chip-touching tool: in-process watchdog armed BEFORE the jax import so
# even a hang during backend init self-exits
# (a hung backend call must end the probe, not hang it)
arm_watchdog(1200, label=__file__)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def make_probe(row_shape, src_shape):
    """Kernel copies src[idx] -> scratch -> out for one dynamic idx."""

    def kernel(idx_ref, src_hbm, out_ref, scratch, sem):
        i = idx_ref[0]
        pltpu.make_async_copy(src_hbm.at[i], scratch, sem).start()
        pltpu.make_async_copy(src_hbm.at[i], scratch, sem).wait()
        flat = scratch[...].reshape(-1)
        out_ref[0, :] = flat[: out_ref.shape[1]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 8), lambda b, idx: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM(row_shape, jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, 8), jnp.float32),
    )
    src = jnp.arange(np.prod(src_shape), dtype=jnp.float32).reshape(src_shape)
    idx = jnp.array([3], jnp.int32)
    return fn, idx, src


CASES = {
    "A_(16,)": (((16,)), (8, 16)),
    "B_(128,)": (((128,)), (8, 128)),
    "C_(1,128)": (((1, 128)), (8, 1, 128)),
    "D_(8,128)": (((8, 128)), (32, 8, 128)),
}


def main():
    print("platform:", jax.devices()[0].platform)
    for name, (row_shape, src_shape) in CASES.items():
        try:
            fn, idx, src = make_probe(row_shape, src_shape)
            out = np.asarray(fn(idx, src))
            base = np.arange(np.prod(src_shape), dtype=np.float32).reshape(
                src_shape)[3].reshape(-1)[:8]
            ok = np.array_equal(out[0], base)
            print(f"{name}: LOWERED ok={ok}")
        except Exception as e:  # noqa: BLE001 - report and move on
            msg = str(e).split("\n")[0][:160]
            print(f"{name}: REJECTED {type(e).__name__}: {msg}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
