"""Placement ``device``: every table in HBM, one jitted step.

Entry: ``DeviceModeModel`` + ``make_device_mode_trainer``
(``persia_tpu/parallel/device_mode.py``) over a DLRM tower, whole-table
``optax.adagrad`` over towers and tables, ids hashed into each table by
the module itself. One process, no services.
"""

import numpy as np

import reference
import weights
from tree_paths import get as _get, put as _set, tower_paths

ROW_RULE = "hashed"


def _leaf_paths(config):
    """weights leaf name -> path in the program's parameter tree."""
    tables = len(config["table_cardinalities"])
    paths = {f"table.{t}": ("DeviceEmbeddingCollection_0", f"bag_C{t + 1}",
                            "table") for t in range(tables)}
    paths.update(tower_paths(config, prefix=("tower",)))
    return paths


class Runner:
    def __init__(self, env):
        import jax
        import optax

        from persia_tpu.models import DLRM
        from persia_tpu.parallel.device_mode import (
            DeviceModeModel,
            make_device_mode_trainer,
        )
        from persia_tpu.parallel.mesh import make_mesh, shard_batch_pytree

        self._jax, self._shard = jax, shard_batch_pytree
        cfg, self.env = env.config, env
        self.opt = cfg["dense_optimizer"]
        self.mesh = make_mesh(tuple(env.mesh_shape), devices=env.devices)
        self.rows = weights.table_rows(cfg, env.max_ind_range,
                                       multiple_of=env.mesh_shape[1])
        self.specs = weights.leaf_specs(cfg, self.rows)
        self.paths = _leaf_paths(cfg)
        self.slot_names = [f"C{t + 1}" for t in range(len(self.rows))]
        dim = cfg["embedding_dim"]
        model = DeviceModeModel(
            slot_specs=[(n, r, dim)
                        for n, r in zip(self.slot_names, self.rows)],
            tower=DLRM(embedding_dim=dim,
                       bottom_mlp=tuple(cfg["bottom_mlp"][:-1]),
                       top_mlp=tuple(cfg["top_mlp"][:-1])))
        sample = self.convert(env.stream.batch(0))
        non_id, ids, _ = self._place(sample)
        optimizer = optax.adagrad(
            self.opt["lr"],
            initial_accumulator_value=self.opt["initial_accumulator"],
            eps=self.opt["eps"])
        params, self.opt_state, self._step = make_device_mode_trainer(
            model, optimizer, self.mesh, non_id, ids,
            seed=env.seed % 2147483647)
        env.mark("program's trainer built (init, optimizer state)")
        # the benchmark's weights take the place of the program's own:
        # free those first, so that the peak is the program's at run time
        shardings = {n: _get(params, p).sharding
                     for n, p in self.paths.items()}
        for n, shape, _ in self.specs:
            have = _get(params, self.paths[n]).shape
            if tuple(have) != tuple(shape):
                raise RuntimeError(f"leaf {n}: program {have}, "
                                   f"configuration {shape}")
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
        mine = weights.make(env.seed, self.specs, shardings)
        for n, p in self.paths.items():
            _set(params, p, mine[n])
        self.params = params
        jax.block_until_ready(mine)
        env.mark("benchmark's weights made and put in")
        self._key = weights.seed_key(env.seed)
        self._grad_sq = self._change_sq = None
        self.program = {}

    # --- feed -----------------------------------------------------------

    def convert(self, b):
        """Generator thread: 1-based int32 id columns (0 is the module's
        padding id)."""
        ids1 = (b["ids"] + 1).astype(np.int32)
        cols = {n: np.ascontiguousarray(ids1[:, t:t + 1])
                for t, n in enumerate(self.slot_names)}
        return b["dense"], cols, b["label"]

    def _place(self, feed):
        dense, cols, label = feed
        placed = self._shard({"n": [dense], "i": cols, "l": label},
                             self.mesh)
        return placed["n"], placed["i"], placed["l"]

    def step(self, feed):
        """Train one batch; returns the loss without waiting for it."""
        non_id, ids, label = self._place(feed)
        with self.mesh:
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, non_id, ids, label)
        return loss

    def settled(self):
        return True

    # --- what `correct` needs from the timed path ------------------------

    def _reducers(self):
        import jax
        import jax.numpy as jnp

        lr, eps = self.opt["lr"], self.opt["eps"]
        specs, paths = self.specs, self.paths

        def grad_sq(params, acc, key):
            out = {}
            for i, (name, shape, kind) in enumerate(specs):
                p0 = weights.gen_leaf(key, i, shape, kind)
                g = ((p0 - _get(params, paths[name]))
                     * jnp.sqrt(_get(acc, paths[name]) + eps) / lr)
                out[name] = jnp.sum(g * g)
            return out

        def change_sq(params, key):
            out = {}
            for i, (name, shape, kind) in enumerate(specs):
                d = (_get(params, paths[name])
                     - weights.gen_leaf(key, i, shape, kind))
                out[name] = jnp.sum(d * d)
            return out

        return jax.jit(grad_sq), jax.jit(change_sq)

    def after_step(self, k, last):
        """Called with step ``k`` finished, before the next is dispatched:
        the first gradient as Adagrad got it, worked out from the state
        after one step (g = (p0 - p1) sqrt(acc1 + eps) / lr; the
        accumulator alone cannot resolve it: 0.1 + g^2 rounds to 0.1), and
        the change of every leaf after the last compared step."""
        if self._grad_sq is None:
            self._grad_sq, self._change_sq = self._reducers()
        with self.mesh:
            if k == 1:
                self.env.mark("first step")
                sq = self._grad_sq(self.params,
                                   self.opt_state[0].sum_of_squares,
                                   self._key)
                self.program["grad_norm"] = {
                    n: float(np.sqrt(v)) for n, v in sq.items()}
            if k == last:
                sq = self._change_sq(self.params, self._key)
                self.program["change_norm"] = {
                    n: float(np.sqrt(v)) for n, v in sq.items()}

    def counters(self):
        return {}

    def table_shapes(self):
        """What the trace reduction knows table work by."""
        return [(r, self.env.config["embedding_dim"]) for r in self.rows]

    def row_ids(self, b):
        """(batch, tables) rows a batch touches, for the bytes the
        embedding work needs."""
        return np.stack(
            [reference.row_index(b["ids"][:, t], r, ROW_RULE)
             for t, r in enumerate(self.rows)], axis=1)

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
    import jax

    cfg = env.config
    rows = weights.table_rows(cfg, env.max_ind_range,
                              multiple_of=env.mesh_shape[1])
    specs = weights.leaf_specs(cfg, rows)
    touched, local = reference.touched_rows(batches, rows, ROW_RULE)

    def build_leaves(key, touched):
        out = {}
        for i, (name, shape, kind) in enumerate(specs):
            if kind == "table":     # only the rows the steps touch
                out[name] = weights.table_rows_of(key, i, touched[i],
                                                  shape[1])
            else:
                out[name] = weights.gen_leaf(key, i, shape, kind)
        return out

    leaves = jax.jit(build_leaves)(weights.seed_key(env.seed), touched)
    sub = [leaves[f"table.{t}"] for t in range(len(rows))]
    mlp = {n: v for n, v in leaves.items() if not n.startswith("table.")}
    opt = cfg["dense_optimizer"]
    return reference.first_steps(cfg, opt, opt, mlp, sub, local, batches,
                                 precision=precision, fault=fault)
