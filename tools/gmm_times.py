"""Time the expert layers' grouped products alone on the chip, at the
parent's one tiling and at the tiling each call is given now.

    python3 tools/gmm_times.py [--tree DIR]
        [--shapes 8192x2304x896x8x64x8xswiglu,...]
        [--tilings parent,chosen,rows128,256x2304x896] [--iters 20]
        [--out FILE]

A shape is tokens x hidden x expert width x experts a token x routed
experts x held experts x activation (an entry of
``models/hybrid_seq.ACTIVATIONS``; default: the expert layers of every
sequence cell of the benchmark, ``kernel_timing.expert_layers``).
Each token picks its experts uniformly at random (seed 0); the held
pairs, sorted by expert, fill the first of
``models/hybrid_seq.pair_buffer_rows`` rows of a buffer, as one pass of
``dispatch_pairs`` sees them. Each tiling runs megablox's three calls as
``ops/grouped_matmul.py`` makes them: ``fwd_ms``, the two products of a
pass (rows x hidden into the first matrix's columns, the activation's
width x the second's into hidden); ``bwd_ms``, their pullbacks (for each
matrix the product with it transposed and the grouped outer product).
``parent`` is (512, 512, 512), cut to each dimension, for every call;
``chosen`` is ``grouped_matmul.tiling`` a call; ``rowsN`` the same rule
with row tiles of N; ``TMxTKxTN`` is that tiling, cut to each
dimension, for every call. Beside them ``least_ms``:
the held rows' multiply-adds over the bfloat16 peak, or the rows in and
out and the held matrices once over the HBM peak where that is more;
``tiles``: each call's tiling; and ``agree``: the largest difference of
a tiling's results from the first tiling's, over the largest magnitude
among them (the outputs are bfloat16, and a float32 sum in another order
can round a value one step of bfloat16 the other way: 0.004 at most). A
tiling the chip's compiler refuses reads ``refused``. Run by no test and
no cell: the yardstick of a change to the tiling rule. Refuses to run
without a TPU.
"""

import sys

import kernel_timing


def main() -> int:
    ap = kernel_timing.parser(__doc__, "")
    ap.add_argument("--tilings", default="parent,chosen")
    args = ap.parse_args()
    found = kernel_timing.chip(args, "gmm_times")
    if found is None:
        return 2
    dev, peaks = found

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    from persia_tpu.models.hybrid_seq import ACTIVATIONS, pair_buffer_rows
    from persia_tpu.ops import grouped_matmul

    bf16 = jnp.bfloat16

    def tiles_of(name, m, k, n, outer):
        if name == "chosen":
            return grouped_matmul.tiling(m, k, n, outer)
        if name.startswith("rows"):
            return grouped_matmul.tiling(m, k, n, outer, rows=int(name[4:]))
        size = (512, 512, 512) if name == "parent" else name.split("x")
        return tuple(min(int(t), d) for t, d in zip(size, (m, k, n)))

    shapes = args.shapes.split(",") if args.shapes else [
        "x".join(map(str, (tokens, hidden, width, per_token, routed, held,
                           activation)))
        for tokens, _, hidden, width, activation, per_token, routed, held
        in kernel_timing.expert_layers(args.tree).values()]
    for shape in shapes:
        *dims, activation = shape.split("x")
        n_tok, hidden, width, per_token, routed, held = map(int, dims)
        first = ACTIVATIONS[activation].wider * width
        rows = pair_buffer_rows(n_tok, per_token, held, routed)
        rng = np.random.default_rng(0)
        picked = np.argsort(rng.random((n_tok, routed)), axis=1)
        local = np.where(picked[:, :per_token] < held,
                         picked[:, :per_token], held).reshape(-1)
        sizes = np.bincount(local, minlength=held + 1)[:held]
        part = np.diff(np.minimum(np.concatenate([[0], np.cumsum(sizes)]),
                                  rows))
        count = int(part.sum())
        sizes = jnp.asarray(np.append(part, rows - count), jnp.int32)
        live = (np.arange(rows) < count)[:, None]

        def normal(*s):
            return jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]), bf16)

        x, mid = normal(rows, hidden), normal(rows, width)
        w1, w2 = normal(held, hidden, first), normal(held, width, hidden)
        g_pre, g_out = normal(rows, first), normal(rows, hidden)
        # (lhs rows, contraction, columns, outer) of the six calls
        shapes = {"w1": (hidden, first, False), "w2": (width, hidden, False),
                  "w2_t": (hidden, width, False),
                  "w1_t": (first, hidden, False),
                  "w2_outer": (width, hidden, True),
                  "w1_outer": (hidden, first, True)}
        mac = count * (hidden * first + width * hidden)
        moved = 2 * (count * (hidden + first + width + hidden)
                     + held * (hidden * first + width * hidden))
        least = max(2 * mac / peaks["bf16_flops_per_s"],
                    moved / peaks["hbm_bytes_per_s"]) * 1e3
        first_got, lines = None, []
        for name in args.tilings.split(","):
            t = {c: tiles_of(name, rows, k, n, outer)
                 for c, (k, n, outer) in shapes.items()}
            if any(rows % tiles[0] for tiles in t.values()):
                print(f"gmm_times: {name} does not tile {rows} rows",
                      file=sys.stderr)
                continue

            @jax.jit
            def forward(x, mid, w1, w2, sizes, t=t):
                return (gmm(x, w1, sizes, bf16, t["w1"]),
                        gmm(mid, w2, sizes, bf16, t["w2"]))

            @jax.jit
            def pullback(x, mid, w1, w2, sizes, g_pre, g_out, t=t):
                return (gmm(g_out, w2, sizes, bf16, t["w2_t"],
                            transpose_rhs=True),
                        tgmm(mid.swapaxes(0, 1), g_out, sizes, bf16,
                             t["w2_outer"], None, held),
                        gmm(g_pre, w1, sizes, bf16, t["w1_t"],
                            transpose_rhs=True),
                        tgmm(x.swapaxes(0, 1), g_pre, sizes, bf16,
                             t["w1_outer"], None, held))

            line = {"device": dev.device_kind, "shape": shape, "rows": rows,
                    "held": count, "tiling": name, "least_ms": least,
                    "tiles": {c: list(v) for c, v in t.items()}}
            try:
                fwd_ms, fwd = kernel_timing.timed(
                    forward, x, mid, w1, w2, sizes, iters=args.iters)
                bwd_ms, bwd = kernel_timing.timed(
                    pullback, x, mid, w1, w2, sizes, g_pre, g_out,
                    iters=args.iters)
            except Exception as e:  # noqa: BLE001 — the compiler's refusal
                line["refused"] = str(e).splitlines()[0][:300]
                lines.append(line)
                continue
            got = [np.where(live, np.asarray(a, np.float32), 0)
                   for a in fwd + bwd[::2]]
            got += [np.asarray(a, np.float32) for a in bwd[1::2]]
            line.update(fwd_ms=fwd_ms, bwd_ms=bwd_ms)
            if first_got is None:
                first_got = got
            else:
                line["agree"] = max(
                    float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                    for a, b in zip(got, first_got))
            lines.append(line)
        kernel_timing.emit(lines, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
