"""Placement ``device_seq_sparse``: the selected-attention sequence
tower on the device placement.

Entry: the one ``device_seq`` uses, ``DeviceModeModel`` +
``make_device_mode_trainer`` over a ``HybridSequenceTower`` with one item
slot that is not pooled, here with the pattern letters ``S`` (grouped-
query attention over the keys a learned indexer selects) and ``E``
(gated experts under a softmax router, no shared expert) and
``loss_fn=next_item_cross_entropy_indexed`` at the configuration's
``index_loss_weight``. One process, no services.

Everything about the feed, the timed step and the readings ``correct``
needs (the first gradient from Adam's first moment, the change after the
compared steps) is ``device_seq``'s ``Runner``; this file gives it
another tower, other leaves, and a probe (one forward pass outside the
step, ``hybrid_seq.sown``) that counts the rows routed to the held
experts and the pairs each ``S`` layer selects.

**Each side selects for itself.** A top-2048 of up to 8192 float32
scores a row falls the other way on bfloat16's noise, and ISSUE 41
provided for a reference run under the program's own selection; read on
the chip (PERF.md section 6, "PR 41") the program's selection differs
from the float32 reference's in 0.45 to 0.88 % of a layer's pairs, all
at the cut, and the gaps read the same either way, so the comparison is
the accepted cells': the reference selects from its own index scores at
its own hidden states, and a program that selected wrongly would show
in every gap.
"""

import collections
import functools

import numpy as np

import reference
import reference_sparse_seq
import weights_sparse_seq as weights
from placements import device_seq
from placements.device_seq import ROW_RULE, SLOT, build_optimizer, fold
from tree_paths import get as _get, put as _set


def tower_sizes(env):
    return weights.sizes_of(env.config, env.sizes.get("tower"))


def leaf_paths(sz):
    """weights leaf name -> path in the program's parameter tree."""
    paths = {"table": ("DeviceEmbeddingCollection_0", f"bag_{SLOT}", "table"),
             "final_norm": ("tower", "final_norm", "weight"),
             "head": ("tower", "item_head")}
    for i, kind in enumerate(sz["pattern"]):
        paths[f"L{i}.norm"] = ("tower", f"layer_{i}", "norm", "weight")
        for p, _, _ in weights.layer_leaves(kind, sz):
            paths[f"L{i}.{p}"] = ("tower", f"layer_{i}", "mixer", p)
    return paths


def build_tower(sz, **more):
    """The program's tower for these sizes."""
    from persia_tpu.models.hybrid_seq import HybridSequenceTower

    return HybridSequenceTower(
        pattern=sz["pattern"], hidden=sz["hidden"], vocab=sz["vocab"],
        eps=sz["eps"], attn_heads=sz["heads"], attn_kv_heads=sz["kv_heads"],
        attn_head_dim=sz["head_dim"], rope_theta=sz["rope_theta"],
        index_heads=sz["index_heads"], index_dim=sz["index_dim"],
        index_rope_dim=sz["index_rope_dim"], select_topk=sz["topk"],
        index_tile=sz["index_tile"],
        experts_routed=sz["experts_routed"],
        experts_held=tuple(sz["experts_held"]),
        experts_per_token=sz["experts_per_token"],
        expert_width=sz["expert_width"], shared_width=0,
        routed_scaling=1.0, expert_activation="swiglu",
        expert_scoring="softmax", **more)


def build_model(sz):
    """The program's model for these sizes."""
    from persia_tpu.parallel.device_mode import DeviceModeModel

    return DeviceModeModel(slot_specs=[(SLOT, sz["vocab"], sz["hidden"])],
                           tower=build_tower(sz), pooling="none")


def loss_of(sz):
    from persia_tpu.parallel.train import next_item_cross_entropy_indexed

    return functools.partial(next_item_cross_entropy_indexed,
                             index_loss_weight=sz["index_loss_weight"])


class Runner(device_seq.Runner):
    def __init__(self, env):
        import jax
        import jax.numpy as jnp

        from persia_tpu.models.hybrid_seq import sown
        from persia_tpu.parallel.device_mode import make_device_mode_trainer
        from persia_tpu.parallel.mesh import make_mesh, shard_batch_pytree

        self._jax, self._shard = jax, shard_batch_pytree
        self.env = env
        self.opt = env.config["optimizer"]
        self.sz = sz = tower_sizes(env)
        self.length = env.mix["session_length"]
        self.mesh = make_mesh(tuple(env.mesh_shape), devices=env.devices)
        self.specs = weights.leaf_specs(sz)
        self.paths = leaf_paths(sz)
        self.model = build_model(sz)
        # no parameter's shape depends on the length, and the trainer's
        # init declares parameters only: give it a short history
        _, ids, _ = self._place(self.convert(
            {k: v[:128] if hasattr(v, "shape") else v
             for k, v in env.stream.batch(0).items()}))
        params, self.opt_state, self._step = make_device_mode_trainer(
            self.model, build_optimizer(self.opt), self.mesh, [], ids,
            loss_fn=loss_of(sz), seed=env.seed % 2147483647)
        env.mark("program's trainer built (init, optimizer state)")
        # the benchmark's weights take the place of the program's own:
        # free those first, so that the peak is the program's at run time
        shardings = {n: _get(params, p).sharding
                     for n, p in self.paths.items()}
        leaves = jax.tree_util.tree_leaves(params)
        if len(leaves) != len(self.specs):
            raise RuntimeError(f"the program has {len(leaves)} leaves, the "
                               f"configuration {len(self.specs)}")
        for n, shape, _ in self.specs:
            have = _get(params, self.paths[n]).shape
            if tuple(have) != tuple(shape):
                raise RuntimeError(f"leaf {n}: program {have}, "
                                   f"configuration {shape}")
        for leaf in leaves:
            leaf.delete()
        mine = weights.make(env.seed, sz, shardings)
        for n, p in self.paths.items():
            _set(params, p, mine[n])
        self.params = params
        jax.block_until_ready(mine)
        env.mark("benchmark's weights made and put in")
        self._key = weights.seed_key(env.seed)
        self._grad_sq = self._change_sq = None

        def probe(params, ids):
            """One forward pass outside the step: the rows routed to the
            held experts (expert layers, held) and the pairs each ``S``
            layer selects (layers,)."""
            rows, keys = sown(self.model, params, [], ids, "intermediates",
                              "selections")
            return rows, jnp.sum(keys, axis=(1, 2, 3), dtype=jnp.int32)

        self._probe = jax.jit(probe)
        self._routed = lambda params, ids: self._probe(params, ids)[0]
        self._recent = collections.deque(maxlen=device_seq.PROBED)
        self._probed = collections.Counter()
        self.program = {}

    def _reducers(self):
        """``device_seq``'s two reductions over this file's leaves (the
        seeded weights have a kind, ``zero``, that its generator lacks)."""
        import jax
        import jax.numpy as jnp

        specs, paths, sz = self.specs, self.paths, self.sz
        b1 = self.opt["b1"]

        def grad_sq(mu):
            return {name: jnp.sum(jnp.square(_get(mu, paths[name])
                                             / (1.0 - b1)))
                    for name, _, _ in specs}

        def change_sq(params, key):
            out = {}
            for i, (name, shape, kind) in enumerate(specs):
                d = (_get(params, paths[name])
                     - weights.gen_leaf(key, i, shape, kind, sz))
                out[name] = jnp.sum(d * d)
            return out

        return jax.jit(grad_sq), jax.jit(change_sq)

    def counters(self):
        """``device_seq``'s running totals (``routed_batches``,
        ``routed_rows_layer_<i>``) and, from the same probes of the last
        batches trained, ``selected_batches`` and
        ``selected_pairs_layer_<i>``: the (query, key) pairs the i-th
        ``S`` layer selected over those batches."""
        with self.mesh:
            for ids in self._recent:
                rows, pairs = self._probe(self.params, ids)
                self._probed["routed_batches"] += 1
                self._probed["selected_batches"] += 1
                for i, n in enumerate(np.asarray(rows).sum(axis=1)):
                    self._probed[f"routed_rows_layer_{i}"] += int(n)
                for i, n in enumerate(np.asarray(pairs)):
                    self._probed[f"selected_pairs_layer_{i}"] += int(n)
        return dict(self._probed)


def build(env):
    return Runner(env)


def reference_side(env, batches, precision="float32", fault=None):
    """The plain reference over the same first batches. Uses nothing of
    the program: weights from the seed, rows by the stated hashing rule."""
    sz = tower_sizes(env)
    folded = []
    for b in batches:
        items, targets = b["items"], b["targets"]
        if fault == "half_batch":
            half = len(items) // 2
            items, targets = items[:half], targets[:half]
        items, targets = fold(items, targets, env.mix["session_length"])
        folded.append((reference.row_index(items, sz["vocab"], ROW_RULE),
                       reference.row_index(targets, sz["vocab"], ROW_RULE)))
    return reference_sparse_seq.first_steps(
        sz, env.config["optimizer"], lambda: weights.make(env.seed, sz),
        folded, precision=precision, fault=fault)
