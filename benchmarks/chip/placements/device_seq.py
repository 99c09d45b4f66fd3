"""Placement ``device_seq``: a sequence tower on the device placement.

Entry: the one the placement ``device`` uses, ``DeviceModeModel`` +
``make_device_mode_trainer`` (``persia_tpu/parallel/device_mode.py``),
over a ``HybridSequenceTower`` with one item slot that is not pooled
(``pooling="none"``), ``loss_fn=next_item_cross_entropy`` and
``optax.adam`` over every leaf, so the trainer builds its dense step.
One process, no services.

The tower's sizes are the configuration's (``weights_hybrid_seq.
sizes_of``) with the cell's ``sizes["tower"]`` laid over them, which only
a rehearsal has. The generator's arrays are flat, one entry an item
event; ``fold`` makes histories of them.

``correct`` compares the first gradient and the change after the
compared steps. With Adam a leaf's change after three steps is about
three learning rates whatever the gradient, so the gradient carries the
test of precision: it is read from the first moment after step one,
``g = mu / (1 - b1)``.
"""

import collections
import sys

import numpy as np

import reference
import reference_hybrid_seq
import weights_hybrid_seq as weights
from tree_paths import get as _get, put as _set

ROW_RULE = "hashed"
SLOT = "item"
# batches whose routing ``counters`` probes: the last ones trained
PROBED = 3


def fold(items, targets, session_length):
    """The generator's flat arrays as histories: (histories, T) with
    ``T = session_length`` where the arrays hold a whole number of
    histories, and all of them as one history otherwise (a rehearsal's
    short batch, the half-batch fault)."""
    n = len(items)
    t = session_length if n % session_length == 0 else n
    return (np.asarray(items).reshape(n // t, t),
            np.asarray(targets).reshape(n // t, t))


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
    """The program's tower for these sizes (``dt_limits`` only shapes the
    benchmark's own weights)."""
    from persia_tpu.models.hybrid_seq import HybridSequenceTower

    return HybridSequenceTower(
        **{k: (tuple(v) if isinstance(v, list) else v)
           for k, v in sz.items() if k != "dt_limits"}, **more)


def build_model(sz):
    """The program's model for these sizes."""
    from persia_tpu.parallel.device_mode import DeviceModeModel

    return DeviceModeModel(slot_specs=[(SLOT, sz["vocab"], sz["hidden"])],
                           tower=build_tower(sz), pooling="none")


def build_optimizer(opt):
    import optax

    return optax.adam(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])


class Runner:
    def __init__(self, env):
        import jax

        from persia_tpu.models.hybrid_seq import routed_rows
        from persia_tpu.parallel.device_mode import make_device_mode_trainer
        from persia_tpu.parallel.mesh import make_mesh, shard_batch_pytree
        from persia_tpu.parallel.train import next_item_cross_entropy

        self._jax, self._shard = jax, shard_batch_pytree
        self.env, cfg = env, env.config
        self.opt = cfg["optimizer"]
        self.sz = sz = tower_sizes(env)
        self.length = env.mix["session_length"]
        self.mesh = make_mesh(tuple(env.mesh_shape), devices=env.devices)
        self.specs = weights.leaf_specs(sz)
        self.paths = leaf_paths(sz)
        self.model = build_model(sz)
        # no parameter's shape depends on the length, and the trainer's
        # init runs the forward eagerly: give it a short history
        _, ids, _ = self._place(self.convert(
            {k: v[:sz["chunk"]] if hasattr(v, "shape") else v
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
        self._recent = collections.deque(maxlen=PROBED)
        self._probed = collections.Counter()
        self.program = {}

    # --- feed -----------------------------------------------------------

    def convert(self, b):
        """Generator thread: histories of 1-based int32 ids (0 is the
        module's padding id) and, as the label, the table row of the
        event that follows each."""
        items, targets = fold(b["items"], b["targets"], self.length)
        rows = reference.row_index(targets, self.sz["vocab"], ROW_RULE)
        return {SLOT: (items + 1).astype(np.int32)}, rows.astype(np.int32)

    def _place(self, feed):
        ids, label = feed
        placed = self._shard({"n": [], "i": ids, "l": label}, self.mesh)
        return placed["n"], placed["i"], placed["l"]

    def step(self, feed):
        """Train one batch; returns the loss without waiting for it."""
        non_id, ids, label = self._place(feed)
        with self.mesh:
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, non_id, ids, label)
        self._recent.append(ids)
        return loss

    def settled(self):
        return True

    # --- what `correct` needs from the timed path ------------------------

    def _reducers(self):
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

    def after_step(self, k, last):
        """Called with step ``k`` finished, before the next is dispatched:
        the first gradient as Adam got it (its first moment after one
        step is (1 - b1) g), and the change of every leaf after the last
        compared step. At the last step also, for the log: the rows each
        held expert was routed, over the expert layers."""
        if self._grad_sq is None:
            self._grad_sq, self._change_sq = self._reducers()
        with self.mesh:
            if k == 1:
                self.env.mark("first step")
                sq = self._grad_sq(self.opt_state[0].mu)
                self.program["grad_norm"] = {
                    n: float(np.sqrt(v)) for n, v in sq.items()}
            if k == last:
                sq = self._change_sq(self.params, self._key)
                self.program["change_norm"] = {
                    n: float(np.sqrt(v)) for n, v in sq.items()}
                rows = np.asarray(self._routed(self.params,
                                               self._recent[-1]))
                print(f"bench: rows routed to a held expert, batch {k - 1}: "
                      f"mean {rows.mean():.1f}, largest {rows.max()}, "
                      f"least {rows.min()} over {rows.shape[0]} expert "
                      f"layers x {rows.shape[1]} experts", flush=True,
                      file=sys.stderr)

    def counters(self):
        """Running totals, so that the harness's difference over the
        window is the probe made after it: ``routed_batches``, the
        batches probed, and ``routed_rows_layer_<i>``, the rows routed to
        the held experts of the i-th expert layer over those batches.
        A call probes the last ``PROBED`` batches trained, with the
        parameters as they are now (``routed_rows``, a forward pass
        outside the step): after the window those are batches of the
        traced seconds, routed as the steps just traced routed them to
        within one to three updates."""
        with self.mesh:
            for ids in self._recent:
                rows = np.asarray(self._routed(self.params, ids))
                self._probed["routed_batches"] += 1
                for i, n in enumerate(rows.sum(axis=1)):
                    self._probed[f"routed_rows_layer_{i}"] += int(n)
        return dict(self._probed)

    def table_shapes(self):
        """What the trace reduction knows table work by."""
        return [(self.sz["vocab"], self.sz["hidden"])]

    def close(self):
        for tree in (self.params, self.opt_state):
            for leaf in self._jax.tree_util.tree_leaves(tree):
                leaf.delete()
        self.params = self.opt_state = None


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
    return reference_hybrid_seq.first_steps(
        sz, env.config["optimizer"], lambda: weights.make(env.seed, sz),
        folded, precision=precision, fault=fault)
