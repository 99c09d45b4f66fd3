#!/usr/bin/env python3
"""Time the parts of selected attention alone on the chip.

    python3 benchmarks/chip/tools/select_times.py [--tree DIR]
        [--shape 1x8192] [--iters 5] [--out FILE]

At ``--shape`` (histories x T; default: what the selected-attention cell
of the benchmark runs a layer) and the configuration's heads (32 query
to 4 key-value heads of 128, 16 index heads of 64, 2048 keys a query,
tiles of 512 queries), the host-clock time, over ``--iters`` calls that
end in ``block_until_ready``, of

- ``scores_ms``: ``ops.sparse_select.index_scores`` over every tile,
  each tile's scores reduced to a row maximum so that nothing else of
  the selection runs;
- ``select_ms``: ``select_keys``, the scores and the selection (the
  bisection and the ties' running count), as a layer's forward pass
  runs them once a step; their difference is the selection's own;
- ``flash_fwd_ms``, ``flash_grad_ms``: ``flash_attention_selected``
  under that selection, forward with ``lse`` and the whole gradient
  (forward, dq, dk/dv), beside ``flash_dense_fwd_ms`` and
  ``flash_dense_grad_ms``, the same kernels without a selection;
- ``target_fwd_ms``, ``target_grad_ms``: ``alignment_loss`` alone (the
  loss: index scores again, the heads' probabilities rebuilt from
  ``lse``) and with its gradients to the indexer's queries, key and
  weights (the forward rule's one pass, which a step runs once);

beside ``selected_least_ms`` and ``index_least_ms`` from
``costs_sparse_seq.py`` for one layer. Inputs as the mixer hands them
over: queries and keys normed a head, the indexer's key layer-normed,
its weights scaled. ``--tree`` times another checkout's ops with the
same script; ``digest`` says whether two trees agree bit for bit.

It is run by no test and no cell: the yardstick of a change to these
ops. Refuses to run without a TPU: a CPU time says nothing about the
chip.
"""

import argparse
import hashlib
import json
import os
import sys
import time

CELL = "keye-vl-2.0-30b-a3b.device-histories8k"


def times(args, tree, lines, peaks):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import costs_sparse_seq
    import manifest
    import weights_sparse_seq as weights
    from persia_tpu.ops import sparse_select
    from persia_tpu.ops.flash_attention import (flash_attention_masked,
                                                flash_attention_selected)

    _, _, config, _ = manifest.Manifest(tree).cell(CELL)
    sz = weights.sizes_of(config)
    bs, t = (int(x) for x in args.shape.split("x"))
    heads, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    ih, idim, topk = sz["index_heads"], sz["index_dim"], sz["topk"]
    tile = sparse_select.tile_of(t, sz["index_tile"])
    rng = np.random.default_rng(t)

    def normal(*sizes):
        return jnp.asarray(rng.normal(size=sizes), jnp.float32)

    def normed(x):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)

    bf16 = jnp.bfloat16
    q = normed(normal(bs, heads, t, hd)).astype(bf16)
    k = normed(normal(bs, kv, t, hd)).astype(bf16)
    v = normal(bs, kv, t, hd).astype(bf16)
    q_i = normal(bs, t, ih, idim).astype(bf16)
    k_i = normed(normal(bs, t, idim)).astype(bf16)
    w = normal(bs, t, ih) * (ih * idim) ** -0.5
    do = normal(bs, heads, t, hd).astype(bf16)
    group = heads // kv

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        jax.block_until_ready(fn(*xs))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            res = fn(*xs)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / args.iters * 1e3, res

    def digest(*arrays):
        h = hashlib.sha256()
        for a in jax.tree_util.tree_leaves(arrays):
            h.update(np.asarray(a.astype(jnp.float32)).tobytes())
        return h.hexdigest()[:16]

    def scores_only(q_i, k_i, w):
        return jax.lax.map(lambda i: jnp.max(sparse_select.index_scores(
            sparse_select._rows(q_i, i, tile, 1), k_i,
            sparse_select._rows(w, i, tile, 1)), axis=-1),
            jnp.arange(t // tile))

    def attend(q, k, v, select):
        return flash_attention_selected(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
            select)

    def dense(q, k, v):
        return flash_attention_masked(q, jnp.repeat(k, group, axis=1),
                                      jnp.repeat(v, group, axis=1),
                                      causal=True)

    line = {"tree": args.tree, "device": jax.devices()[0].device_kind,
            "batch": bs, "t": t, "topk": topk, "tile": tile,
            "selected_least_ms": 1e3 * costs_sparse_seq.
            selected_least_seconds(
                sz, t, [bs * costs_sparse_seq.selected_pairs(t, topk)],
                peaks),
            "index_least_ms": 1e3 * costs_sparse_seq.index_least_seconds(
                dict(sz, pattern="S"), t, bs, peaks)}
    line["scores_ms"], _ = timed(jax.jit(scores_only), q_i, k_i, w)
    line["select_ms"], select = timed(jax.jit(
        lambda *xs: sparse_select.select_keys(*xs, topk, tile)), q_i, k_i, w)
    line["selected_pairs"] = int(jnp.sum(select, dtype=jnp.int32))
    line["flash_fwd_ms"], (out, lse) = timed(jax.jit(attend), q, k, v, select)
    line["flash_grad_ms"], grads = timed(jax.jit(jax.grad(
        lambda q, k, v: jnp.sum((attend(q, k, v, select)[0] * do).astype(
            jnp.float32)), argnums=(0, 1, 2))), q, k, v)
    line["flash_dense_fwd_ms"], _ = timed(jax.jit(dense), q, k, v)
    line["flash_dense_grad_ms"], _ = timed(jax.jit(jax.grad(
        lambda q, k, v: jnp.sum((dense(q, k, v) * do).astype(jnp.float32)),
        argnums=(0, 1, 2))), q, k, v)

    def target(q_i, k_i, w):
        return sparse_select.alignment_loss(q_i, k_i, w, q, k, lse, select,
                                            hd ** -0.5, tile)

    line["target_fwd_ms"], loss = timed(jax.jit(target), q_i, k_i, w)
    line["target_grad_ms"], to_index = timed(jax.jit(jax.value_and_grad(
        target, argnums=(0, 1, 2))), q_i, k_i, w)
    line["alignment_loss"] = float(loss)
    line["digest"] = digest(select, out, lse, grads, to_index)
    out_line = {k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in line.items()}
    lines.append(out_line)
    print(json.dumps(out_line), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))
    ap.add_argument("--shape", default="1x8192")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    sys.path.insert(0, os.path.join(tree, "benchmarks", "chip"))

    import jax

    import costs

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("select_times: no TPU here, nothing timed", file=sys.stderr)
        return 2
    lines = []
    times(args, tree, lines, costs.peaks_for(
        os.path.join(tree, "benchmarks", "chip"), dev.device_kind))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
