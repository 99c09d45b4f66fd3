"""Placement ``device_seq_window``: the sliding-window sequence tower on
the device placement.

Entry: the one ``device_seq`` uses, ``DeviceModeModel`` +
``make_device_mode_trainer`` over a ``HybridSequenceTower`` with one item
slot that is not pooled and ``loss_fn=next_item_cross_entropy``, here
with the pattern letters ``W`` (grouped-query attention over a sliding
window, plain rotary), ``A`` (grouped-query attention over every earlier
key under a YaRN rule) and ``E`` (gated experts under a softmax router,
no shared expert). One process, no services.

Everything about the feed, the timed step, the readings ``correct``
needs (the first gradient from Adam's first moment, the change after the
compared steps) and the routed-rows probe is ``device_seq``'s ``Runner``;
this file gives it another tower and other leaves. The reference is
``reference_window_seq.py``.
"""

import collections

import reference
import reference_window_seq
import weights_window_seq as weights
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


def yarn_rule(yarn):
    """The published YaRN keys as the program's rule: mscale 1 and no
    mscale_all_dim, whose amplitude 0.1 ln(factor) + 1 is the config's
    ``attention_factor`` (``weights_window_seq.sizes_of`` holds it so)
    and whose softmax scale stays ``head_dim^(-1/2)``."""
    from persia_tpu.models.hybrid_seq import YarnRule

    return YarnRule(factor=yarn["factor"],
                    original_positions=yarn["original_positions"],
                    beta_fast=yarn["beta_fast"], beta_slow=yarn["beta_slow"],
                    mscale=1.0, mscale_all_dim=0.0)


def build_tower(sz, **more):
    """The program's tower for these sizes."""
    from persia_tpu.models.hybrid_seq import HybridSequenceTower

    tower = dict(
        pattern=sz["pattern"], hidden=sz["hidden"], vocab=sz["vocab"],
        eps=sz["eps"], attn_heads=sz["heads"], attn_kv_heads=sz["kv_heads"],
        attn_head_dim=sz["head_dim"], attn_window=sz["window"],
        rope_theta=sz["rope_theta"], rope_scaling=yarn_rule(sz["yarn"]),
        experts_routed=sz["experts_routed"],
        experts_held=tuple(sz["experts_held"]),
        experts_per_token=sz["experts_per_token"],
        expert_width=sz["expert_width"], shared_width=0,
        routed_scaling=1.0, expert_activation="swiglu",
        expert_scoring="softmax")
    tower.update(more)
    return HybridSequenceTower(**tower)


def build_model(sz, **more):
    """The program's model for these sizes."""
    from persia_tpu.parallel.device_mode import DeviceModeModel

    return DeviceModeModel(slot_specs=[(SLOT, sz["vocab"], sz["hidden"])],
                           tower=build_tower(sz, **more), pooling="none")


class Runner(device_seq.Runner):
    def __init__(self, env):
        import jax

        from persia_tpu.models.hybrid_seq import routed_rows
        from persia_tpu.parallel.device_mode import make_device_mode_trainer
        from persia_tpu.parallel.mesh import make_mesh, shard_batch_pytree
        from persia_tpu.parallel.train import next_item_cross_entropy

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
            loss_fn=next_item_cross_entropy, seed=env.seed % 2147483647)
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
        self._routed = jax.jit(
            lambda params, ids: routed_rows(self.model, params, [], ids))
        self._recent = collections.deque(maxlen=device_seq.PROBED)
        self._probed = collections.Counter()
        self.program = {}


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
    return reference_window_seq.first_steps(
        sz, env.config["optimizer"], lambda: weights.make(env.seed, sz),
        folded, precision=precision, fault=fault)
