"""Time the movements of an expert layer's pair rows alone on the chip.

    python3 tools/expert_rows_times.py [--tree DIR]
        [--shapes 8192x2304x8x64x8,8192x2048x4x64x8]
        [--paths kernel,plain] [--iters 20] [--out FILE]

A shape is tokens x hidden x experts a token x routed experts x held
experts (default: the mellum and glm cells'). Each token picks its
experts uniformly at random (seed 0); the held pairs, sorted by expert,
fill the first of ``models/hybrid_seq.pair_buffer_rows`` rows of a
buffer, as one pass of ``dispatch_pairs`` sees them. For each path
(``kernel``: ``ops.expert_rows``' Pallas kernels; ``plain``: XLA's
gather and scatter-add, the form of shapes the kernels do not take)
the host-clock time of one pass's movements over ``--iters`` calls that
end in ``block_until_ready``: ``fwd_ms``, the token rows gathered into
the buffer (bfloat16) and the product's rows, weighted,
added onto the tokens; ``bwd_ms``, the token rows and the cotangent's
(float32) gathered and the pullback's rows added onto the tokens. Beside
them ``least_ms``: the held rows read and written once in their dtypes
(forward: a bfloat16 row read and written, a bfloat16 row read and a
float32 row read and written a pair; backward: the same plus a float32
row read and written) over the HBM peak, and ``agree``: the largest
difference between the kernels' results and the plain form's, which
should be 0 (rows are copied; a token's sums are added in the buffer's
order). Run by no test and no cell: the yardstick of a change to the
kernels. Refuses to run without a TPU.
"""

import sys

import kernel_timing


def main() -> int:
    ap = kernel_timing.parser(__doc__, "8192x2304x8x64x8,8192x2048x4x64x8")
    ap.add_argument("--paths", default="kernel,plain")
    args = ap.parse_args()
    found = kernel_timing.chip(args, "expert_rows_times")
    if found is None:
        return 2
    dev, peaks = found
    hbm = peaks["hbm_bytes_per_s"]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from persia_tpu.models.hybrid_seq import pair_buffer_rows
    from persia_tpu.ops.expert_rows import PairRows

    f32, bf16 = jnp.float32, jnp.bfloat16

    def timed(f, *a):
        return kernel_timing.timed(f, *a, iters=args.iters)

    for shape in args.shapes.split(","):
        n, hidden, k, routed, held = map(int, shape.split("x"))
        rows = pair_buffer_rows(n, k, held, routed)
        rng = np.random.default_rng(0)
        chosen = np.argsort(rng.random((n, routed)), axis=1)[:, :k]
        local = np.where(chosen < held, chosen, held).reshape(-1)
        order = np.argsort(local, kind="stable")[:rows]
        sizes = np.bincount(local, minlength=held + 1)[:held]
        part = np.diff(np.minimum(np.concatenate([[0], np.cumsum(sizes)]),
                                  rows))
        count = int(part.sum())
        sizes = jnp.asarray(np.append(part, rows - count), jnp.int32)
        token_of = jnp.asarray(order // k, jnp.int32)
        tokens = jnp.asarray(rng.normal(size=(n, hidden)), bf16)
        ct = jnp.asarray(rng.normal(size=(n, hidden)), f32)
        out = jnp.asarray(rng.normal(size=(rows, hidden)), bf16)
        weight = jnp.asarray(rng.random(rows), f32)
        live = (np.arange(rows) < count)[:, None]
        least = {"fwd": count * hidden * (2 + 2 + 2 + 8) / hbm * 1e3,
                 "bwd": count * hidden * (2 + 2 + 8 + 2 + 8) / hbm * 1e3}
        got, lines = {}, []
        for path in args.paths.split(","):
            moves = PairRows(path == "kernel")

            @jax.jit
            def forward(tokens, out, weight, token_of, sizes, moves=moves):
                buf = moves.take(tokens, token_of, sizes)
                acc = moves.add(jnp.zeros(tokens.shape, f32), out, weight,
                                token_of, sizes)
                return buf, acc

            @jax.jit
            def backward(tokens, ct, out, token_of, sizes, moves=moves):
                buf = moves.take(tokens, token_of, sizes)
                g = moves.take(ct, token_of, sizes)
                acc = moves.add(jnp.zeros(tokens.shape, f32), out,
                                jnp.ones(out.shape[0], f32), token_of,
                                sizes)
                return buf, g, acc

            live_w = jnp.where(live[:, 0], weight, 0.0)
            fwd_ms, fwd = timed(forward, tokens, out, live_w, token_of,
                                sizes)
            bwd_ms, bwd = timed(backward, tokens, ct,
                                jnp.where(live, out, 0).astype(bf16),
                                token_of, sizes)
            got[path] = [np.where(live, np.asarray(fwd[0], np.float32), 0),
                         np.asarray(fwd[1]),
                         np.where(live, np.asarray(bwd[1]), 0),
                         np.asarray(bwd[2])]
            lines.append({
                "device": dev.device_kind, "shape": shape, "rows": rows,
                "held": count, "path": path, "fwd_ms": fwd_ms,
                "bwd_ms": bwd_ms, "fwd_least_ms": least["fwd"],
                "bwd_least_ms": least["bwd"]})
        if len(got) == 2:
            lines[-1]["agree"] = max(
                float(np.max(np.abs(a - b)))
                for a, b in zip(got["kernel"], got["plain"]))
        kernel_timing.emit(lines, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
