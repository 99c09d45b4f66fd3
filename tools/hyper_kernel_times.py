"""Time the hyper-connection kernels alone on the chip, one call each.

    python3 tools/hyper_kernel_times.py [--shapes 8192x4x3584,...]
        [--blocks default,128:256x896,...] [--dtype bfloat16] [--out FILE]

For each shape (positions x streams x features a stream: the state is
positions x streams * features; default the xing cell's 8192 x 4 x 3584)
and each block choice (``default``: the constants of
``ops/hyper_connection.py``; else ``<read-out rows>:<rows>x<features>``
for the whole-row kernel and the feature-blocked ones), the host-clock
time of each of the five kernels as the step calls it, jitted entry and
all (``read_out``, ``mix``, ``mix_bwd``, ``dpre``, ``read_out_bwd``), in
ms a call over ``--iters`` calls that end in ``block_until_ready``,
beside the bytes the call has to move (state-sized arrays, the mixer's
``y`` and ``dy`` in the state's dtype and the read-out's ``u`` and
``du`` in float32; not the (positions, 4..24) maps) over the HBM peak. ``sublayer_ms`` is one sublayer of a training step: every
kernel once and the read-out once more, which ``nn.remat`` runs again.
Refuses to run without a TPU: a CPU time says nothing about a kernel.
"""

import argparse
import json
import os
import sys
import time

HBM_BYTES_PER_S = 819e9     # one TPU v5e chip (benchmarks/chip/peaks.json)

# what each kernel reads and writes: states, (positions, features)
# arrays in the state's dtype (y, dy), and in float32 (u, du)
PASSES = {"read_out": (1, 0, 1), "mix": (2, 1, 0), "mix_bwd": (3, 2, 0),
          "dpre": (1, 0, 1), "read_out_bwd": (3, 0, 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="8192x4x3584")
    ap.add_argument("--blocks", default="default")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from persia_tpu.ops import hyper_connection as hc

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("hyper_kernel_times: no TPU here, nothing timed",
              file=sys.stderr)
        return 2

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        jax.block_until_ready(fn(*xs))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3

    dtype = jnp.dtype(args.dtype)
    lines = []
    for shape in args.shapes.split(","):
        t, n, c = (int(v) for v in shape.split("x"))
        w = 2 * n + n * n
        rng = np.random.default_rng(t + n + c)

        def normal(*dims, dtype=jnp.float32, scale=1.0):
            return jnp.asarray(rng.normal(size=dims) * scale, dtype)

        x, g, dcarry = (normal(t, n * c, dtype=dtype) for _ in range(3))
        y, du = normal(t, c, dtype=dtype), normal(t, c)
        phi = normal(n * c, w, scale=0.02)
        scale, bias = jnp.full((1,), 0.5), normal(n, scale=0.3)
        res = jax.nn.softmax(normal(t, n, n), axis=-1).reshape(t, n * n)
        post, pre = (jax.nn.sigmoid(normal(t, n)) for _ in range(2))
        factor, rdm = normal(t, 1, scale=1e-3), normal(t, w, scale=1e-2)
        for blocks in args.blocks.split(","):
            read, how = {}, {}
            if blocks != "default":
                rows, rest = blocks.split(":")
                read = {"rows": int(rows)}
                how = dict(zip(("rows", "features"),
                               (int(v) for v in rest.split("x"))))
            calls = {
                "read_out": (lambda: hc.read_out_fwd(
                    x, phi, scale, bias, streams=n, eps=1e-6,
                    interpret=False, **read)),
                "mix": (lambda: hc.mix_fwd(
                    x, y, res, post, interpret=False, **how)),
                "mix_bwd": (lambda: hc.mix_bwd(
                    x, y, res, post, g, interpret=False, **how)),
                "dpre": (lambda: hc.read_out_dpre(
                    x, du, streams=n, interpret=False, **how)),
                "read_out_bwd": (lambda: hc.read_out_bwd(
                    dcarry, x, du, pre, factor, rdm, phi, streams=n,
                    interpret=False, **how)),
            }
            line = {"device": dev.device_kind, "positions": t, "streams": n,
                    "features": c, "dtype": dtype.name, "blocks": blocks}
            total = 0.0
            for name, call in calls.items():
                states, narrow, wide = PASSES[name]
                moved = t * c * ((states * n + narrow) * dtype.itemsize
                                 + wide * 4)
                least = moved / HBM_BYTES_PER_S * 1e3
                try:
                    ms = timed(call)
                    line[name] = {"ms": round(ms, 4),
                                  "least_ms": round(least, 4),
                                  "of_hbm_peak": round(least / ms, 4)}
                    total += ms * (2 if name == "read_out" else 1)
                except Exception as e:  # noqa: BLE001 — the chip refuses
                    line[name] = {"refused": str(e)[:300]}
                    total = float("nan")
            line["sublayer_ms"] = round(total, 4)
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
