"""Time the Kimi Delta Attention recurrence alone on the chip.

    python3 tools/kda_times.py [--tree DIR] [--shapes 1x8192x32x128]
        [--chunks 32,64,128] [--out FILE]

For each shape (batch x T x heads x head width, keys and values alike;
default: what the delta-rule sequence cell of the benchmark runs a
layer) and each chunk, the host-clock time of ``ops.kda_scan.kda_scan``
forward (one Pallas call, which also writes the states that enter the
chunks) and of its whole gradient (all five inputs: the forward call,
then the backward call, which rebuilds each chunk's matrices from its
inputs and the kept states; nothing names what is kept here, so this is
the time of a layer whose forward ran once), over ``--iters`` calls
that end in ``block_until_ready``, beside ``least_ms``: the larger of
the recurrence's operations over the bf16 peak and the bytes of its
inputs, its output and their gradients over the HBM peak
(``benchmarks/chip/costs_kda_seq.kda_least_seconds`` for one layer).
Inputs as the mixer hands them over: ``q`` and ``k`` normed a head, the
query scaled, ``g`` from the published ranges of ``A_log`` and
``dt_bias``. ``--tree`` times another checkout's op with the same
script; the ``digest`` of output and gradients says whether two trees
agree bit for bit. It is run by no test and no cell: the yardstick of a
change to the op. Refuses to run without a TPU: a CPU time says nothing
about the chip.
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
    ap.add_argument("--shapes", default="1x8192x32x128")
    ap.add_argument("--chunks", default="32,64,128")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    sys.path.insert(0, os.path.join(tree, "benchmarks", "chip"))

    import jax
    import jax.numpy as jnp
    import numpy as np

    import costs
    import costs_kda_seq
    from persia_tpu.ops.kda_scan import kda_gate, kda_scan

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("kda_times: no TPU here, nothing timed", file=sys.stderr)
        return 2
    peaks = costs.peaks_for(os.path.join(tree, "benchmarks", "chip"),
                            dev.device_kind)

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
        bs, t, heads, hd = (int(x) for x in shape.split("x"))
        rng = np.random.default_rng(heads * 1000 + hd)

        def normal(*sizes):
            return jnp.asarray(rng.normal(size=sizes), jnp.float32)

        def unit(x):
            return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

        dt = jnp.exp(jnp.asarray(rng.uniform(
            np.log(1e-3), np.log(1e-1), size=(heads * hd,)), jnp.float32))
        g = kda_gate(normal(bs, t, heads, hd),
                     jnp.log(jnp.asarray(rng.uniform(1, 16, size=(heads,)),
                                         jnp.float32)),
                     dt + jnp.log(-jnp.expm1(-dt)))
        q = (unit(normal(bs, t, heads, hd)) * hd ** -0.5).astype(jnp.bfloat16)
        k = unit(normal(bs, t, heads, hd)).astype(jnp.bfloat16)
        v = normal(bs, t, heads, hd).astype(jnp.bfloat16)
        beta = jax.nn.sigmoid(normal(bs, t, heads))
        do = normal(bs, t, heads, hd)
        least = costs_kda_seq.kda_least_seconds(
            {"kda_heads": heads, "kda_head_dim": hd, "pattern": "K"}, t, bs,
            peaks)
        for chunk in (int(c) for c in args.chunks.split(",")):
            line = {"tree": args.tree, "device": dev.device_kind,
                    "batch": bs, "t": t, "heads": heads, "width": hd,
                    "chunk": chunk, "least_ms": least * 1e3}

            def op(*xs):
                return kda_scan(*xs, chunk=chunk)

            try:
                line["fwd_ms"], out = timed(jax.jit(op), q, k, v, g, beta)
                line["grad_ms"], grads = timed(jax.jit(jax.grad(
                    lambda *xs: jnp.sum(op(*xs) * do),
                    argnums=(0, 1, 2, 3, 4))), q, k, v, g, beta)
                line["digest"] = digest(out, *grads)
            except Exception as e:  # noqa: BLE001 — a size refused
                line["refused"] = str(e)[:300]
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
