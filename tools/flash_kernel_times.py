"""Time the flash-attention kernels alone on the chip, one call each.

    python3 tools/flash_kernel_times.py [--tree DIR]
        [--blocks default,512x512,...]
        [--shapes 20x8192x256,32x8192x128,32x8192x192/128] [--out FILE]

For each shape (heads x T x head width, or key width / value width
where the two differ; batch 1, bfloat16, causal: what the three
attention-bearing sequence cells of the benchmark run) and each block size
(``default``: whatever the kernel file picks for the call), the
host-clock time of the forward as serving calls it, the forward with
``lse`` as training calls it, the backward's dq call and its dk/dv call
each alone (the other is dead code to the compiler; delta's reduce
rides with both) and the whole gradient, over ``--iters`` calls that end
in ``block_until_ready``. ``--tree`` times another checkout's kernels
(the parent's archive) with the same script; the ``digest`` of outputs
and gradients says whether two trees agree bit for bit. Refuses to run
without a TPU: a CPU time says nothing about a kernel.
"""

import argparse
import hashlib
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--shapes",
                    default="20x8192x256,32x8192x128,32x8192x192/128")
    ap.add_argument("--blocks", default="default")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from persia_tpu.ops import flash_attention as fa

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("flash_kernel_times: no TPU here, nothing timed",
              file=sys.stderr)
        return 2

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        jax.block_until_ready(fn(*xs))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3, out

    def digest(*arrays):
        h = hashlib.sha256()
        for a in arrays:
            h.update(np.asarray(a.astype(jnp.float32)).tobytes())
        return h.hexdigest()[:16]

    lines = []
    for shape in args.shapes.split(","):
        h, t, widths = shape.split("x")
        h, t = int(h), int(t)
        dh, dv = (int(x) for x in (widths.split("/") * 2)[:2])
        rng = np.random.default_rng(h * 1000 + dh)
        q, k, v, do = (jnp.asarray(rng.normal(size=(1, h, t, width)),
                                   jnp.bfloat16)
                       for width in (dh, dh, dv, dv))
        for blocks in args.blocks.split(","):
            kw = {"causal": True}
            if blocks != "default":
                kw["block_q"], kw["block_k"] = (
                    int(x) for x in blocks.split("x"))
            line = {"tree": args.tree, "device": dev.device_kind,
                    "heads": h, "t": t, "dh": dh, "dv": dv,
                    "blocks": blocks}
            try:
                fwd = jax.jit(lambda q, k, v: fa.flash_attention_fwd_pallas(
                    q, k, v, **kw))
                fwd_lse = jax.jit(
                    lambda q, k, v: fa.flash_attention_fwd_pallas(
                        q, k, v, return_lse=True, **kw))
                line["fwd_ms"], _ = timed(fwd, q, k, v)
                line["fwd_lse_ms"], (out, lse) = timed(fwd_lse, q, k, v)
                bwd = [jax.jit(lambda *xs, pick=pick: pick(
                    fa.flash_attention_bwd_pallas(*xs, **kw)))
                    for pick in (lambda g: g[0], lambda g: g[1:],
                                 lambda g: g)]
                res = (q, k, v, out, lse, do)
                line["dq_ms"], _ = timed(bwd[0], *res)
                line["dkv_ms"], _ = timed(bwd[1], *res)
                line["bwd_ms"], grads = timed(bwd[2], *res)
                line["grad_ms"], _ = timed(jax.jit(jax.grad(
                    lambda q, k, v: (fa.flash_attention_masked(
                        q, k, v, **kw).astype(jnp.float32)
                        * do.astype(jnp.float32)).sum(),
                    argnums=(0, 1, 2))), q, k, v)
                line["digest"] = digest(out, lse, *grads)
            except Exception as e:  # noqa: BLE001 — a size the chip refuses
                line["refused"] = str(e)[:300]
            if hasattr(fa, "metrics"):
                reg = fa.metrics.default_registry()
                line["pairs"] = {n: reg.gauge(
                    f"flash_attention_pairs_{n}").value
                    for n in ("walked", "dense", "edge")}
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
