"""Time the index scores' two kernels alone on the chip.

    python3 tools/index_kernel_times.py [--shapes 1x8192x16x64]
        [--tile 512] [--blocks default,256x256] [--out FILE]

For each shape (histories x T x index heads x width; default: what the
selected-attention cell of the benchmark runs a layer) in tiles of
``--tile`` queries, the host-clock time, over ``--iters`` calls that end
in ``block_until_ready``, of a layer's sixteen tiles through
``ops.sparse_select.index_scores`` each at its own position (``fwd_ms``:
the forward kernel; a tile's scores reduced to a row maximum so that
nothing else runs) and through its pullback under a cotangent that is
zero after each query (``pull_ms``: forward and pullback kernels, the
three gradients summed as ``_alignment`` sums them), beside the same
through ``index_scores_plain`` (``plain_fwd_ms``, ``plain_pull_ms``:
every key multiplied, the ``(heads, tile, T)`` products in HBM) and
``least_ms`` (``costs_sparse_seq.index_least_seconds`` for one layer).
A block choice is ``<queries>x<keys>`` a grid step; ``default`` is the
module's. One the default 16 MB of VMEM does not hold reads
``refused``. It is run by no test and no cell: the yardstick of a change to the
kernels. Refuses to run without a TPU: a CPU time says nothing about the
chip.
"""

import argparse
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--shapes", default="1x8192x16x64")
    ap.add_argument("--tile", type=int, default=512)
    ap.add_argument("--blocks", default="default")
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
    import costs_sparse_seq
    from persia_tpu.ops import sparse_select

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("index_kernel_times: no TPU here, nothing timed",
              file=sys.stderr)
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

    lines = []
    for shape in args.shapes.split(","):
        bs, t, heads, width = (int(x) for x in shape.split("x"))
        tile = sparse_select.tile_of(t, args.tile)
        rng = np.random.default_rng(heads * 1000 + width)
        q_i = jnp.asarray(rng.normal(size=(bs, t, heads, width)),
                          jnp.bfloat16)
        k_i = jnp.asarray(rng.normal(size=(bs, t, width)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(bs, t, heads)),
                        jnp.float32) * (heads * width) ** -0.5
        to_scores = jnp.where(
            jnp.tril(jnp.ones((t, t), bool)),
            jnp.asarray(rng.normal(size=(bs, t, t)), jnp.float32), 0.0)
        rows = sparse_select._rows

        def layer(scores_of, pull):
            def forward(q_i, k_i, w):
                return jax.lax.map(lambda i: jnp.max(scores_of(
                    rows(q_i, i, tile, 1), k_i, rows(w, i, tile, 1), i),
                    axis=-1), jnp.arange(t // tile))

            def pulled(q_i, k_i, w, to_scores):
                def one(to_k, i):
                    _, back = jax.vjp(
                        lambda *xs: scores_of(*xs, i), rows(q_i, i, tile, 1),
                        k_i, rows(w, i, tile, 1))
                    to_q, to_k_t, to_w = back(rows(to_scores, i, tile, 1))
                    return to_k + to_k_t.astype(jnp.float32), (to_q, to_w)
                return jax.lax.scan(one, jnp.zeros(k_i.shape, jnp.float32),
                                    jnp.arange(t // tile))

            return jax.jit(pulled if pull else forward)

        def kernels(q_t, k_i, w_t, i):
            return sparse_select.index_scores(q_t, k_i, w_t, i * tile)

        def plain(q_t, k_i, w_t, i):
            return sparse_select.index_scores_plain(q_t, k_i, w_t)

        base = {"device": dev.device_kind, "batch": bs, "t": t,
                "heads": heads, "width": width, "tile": tile,
                "least_ms": 1e3 * costs_sparse_seq.index_least_seconds(
                    {"index_heads": heads, "index_dim": width,
                     "pattern": "S"}, t, bs, peaks)}
        base["plain_fwd_ms"], _ = timed(layer(plain, False), q_i, k_i, w)
        base["plain_pull_ms"], want = timed(layer(plain, True), q_i, k_i, w,
                                            to_scores)
        for choice in args.blocks.split(","):
            line = dict(base, blocks=choice)
            if choice != "default":
                queries, keys = choice.split("x")
                sparse_select.BLOCK_Q = int(queries)
                sparse_select.BLOCK_K = (int(keys),)
            line["held"] = sparse_select.index_blocks(tile, t)
            try:
                line["fwd_ms"], _ = timed(layer(kernels, False), q_i, k_i, w)
                line["pull_ms"], got = timed(layer(kernels, True), q_i, k_i,
                                             w, to_scores)
                line["gap"] = max(
                    float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32)))
                          / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in zip(jax.tree_util.tree_leaves(got),
                                    jax.tree_util.tree_leaves(want)))
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
