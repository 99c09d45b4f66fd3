"""Placement ``cached``: rows on host parameter servers behind an HBM
cache.

Entry: ``ServiceCtx(n_ps=2, n_workers=0)`` (real PS subprocesses) -> an
in-process ``EmbeddingWorker`` over ``PsClient``s -> ``TrainCtx(device_
cache_capacity=...)`` -> ``ctx.train_step`` on raw ``PersiaBatch``es, as
``chip_smoke.run_sparse_phase(cached=True)`` drives it (the only topology
the cache engine supports). For a cached ctx the program's ``DataLoader``
passes the dataset's batches through untouched, so the benchmark's
prefetcher stands where it would. Native store and native middleware are
required; the native library is built with plain ``make``, so only the
first run of a checkout pays for it.

The benchmark makes the weights: the tower's leaves on the device from the
seed, and the rows of the compared steps' signs by ``weights.hashed_rows``,
written to the parameter servers before step 1 (every later row is the
servers' own first-touch row and is not compared). What the program did to
them is read back through ``flush_device_cache()`` and the servers, so the
comparison covers the write-back too.
"""

import hashlib
import json
import os
import subprocess
import urllib.request

import numpy as np

import reference
import weights
from tree_paths import get as _get, put as _set, tower_paths as _tower_paths

ROW_RULE = "exact"


def _offsets(config):
    """Per-table base of the sign space: ranges disjoint, sign 0 unused."""
    cards = np.asarray(config["table_cardinalities"], np.int64)
    return np.concatenate([[0], np.cumsum(cards)])[:-1] + 1


def _unique_ids(batches, tables):
    return [np.unique(np.concatenate([b["ids"][:, t] for b in batches]))
            for t in range(tables)]


def _padded(ids, n):
    """Repeat the last id up to a fixed length: the reference's shapes are
    then the same for every seed, and its compiled step is found again."""
    return np.concatenate([ids, np.full(n - len(ids), ids[-1], ids.dtype)])


def _build_native(root):
    """The native library, built on THIS machine (the Makefile compiles
    with -march=native, and one built elsewhere kills the PS children
    with an illegal instruction before they log a line). A marker beside
    it names the CPU it was built on: the same CPU means plain ``make``,
    so only the first run of a checkout pays for the build."""
    with open("/proc/cpuinfo") as f:
        cpu = hashlib.sha256("".join(
            line for line in f
            if line.startswith(("model name", "flags"))).encode()).hexdigest()
    marker = os.path.join(root, "native", "build", ".built_on")
    stale = True
    if os.path.exists(marker):
        with open(marker) as f:
            stale = f.read().strip() != cpu
    subprocess.run(["make", "-C", os.path.join(root, "native")]
                   + (["-B"] if stale else [])
                   + ["build/libpersia_native.so"], check=True,
                   stdout=subprocess.DEVNULL)
    with open(marker, "w") as f:
        f.write(cpu)


class Runner:
    def __init__(self, env):
        import jax
        import optax

        from persia_tpu.config import EmbeddingSchema, uniform_slots
        from persia_tpu.ctx import TrainCtx
        from persia_tpu.data.batch import (
            IDTypeFeatureWithSingleID,
            Label,
            NonIDTypeFeature,
            PersiaBatch,
        )
        from persia_tpu.embedding import EmbeddingConfig
        from persia_tpu.embedding.optim import Adagrad
        from persia_tpu.metrics import default_registry
        from persia_tpu.models import DLRM
        from persia_tpu.parallel.train import create_train_state
        from persia_tpu.ps.native import load_native_lib
        from persia_tpu.service.helper import ServiceCtx
        from persia_tpu.service.ps_service import PsClient
        from persia_tpu.worker import mw_native
        from persia_tpu.worker.worker import EmbeddingWorker

        self._jax, self.env = jax, env
        cfg = env.config
        self.dim = cfg["embedding_dim"]
        self.tables = len(cfg["table_cardinalities"])
        self.offsets = _offsets(cfg)
        self.names = [f"C{t + 1}" for t in range(self.tables)]
        self.row_opt, self.opt = env.cell["row_optimizer"], \
            cfg["dense_optimizer"]
        self._batch_types = (PersiaBatch, IDTypeFeatureWithSingleID,
                             NonIDTypeFeature, Label)
        _build_native(env.manifest.root)
        if load_native_lib(build_if_missing=False) is None or \
                not mw_native.available():
            raise RuntimeError("the native store or middleware did not load")
        env.mark("native library built and loaded")
        schema = EmbeddingSchema(
            slots_config=uniform_slots(self.names, dim=self.dim))
        self._svc = ServiceCtx(schema, n_workers=0, n_ps=2, http_all=True)
        self._svc.__enter__()
        self._ctx = self._tower_fn = None
        try:
            self.worker = EmbeddingWorker(
                schema, [PsClient(a) for a in self._svc.ps_addrs])
            model = DLRM(embedding_dim=self.dim,
                         bottom_mlp=tuple(cfg["bottom_mlp"][:-1]),
                         top_mlp=tuple(cfg["top_mlp"][:-1]))
            optimizer = optax.adagrad(
                self.opt["lr"],
                initial_accumulator_value=self.opt["initial_accumulator"],
                eps=self.opt["eps"])
            self._ctx = TrainCtx(
                model=model, dense_optimizer=optimizer,
                embedding_optimizer=Adagrad(
                    lr=self.row_opt["lr"],
                    initial_accumulator_value=self.row_opt[
                        "initial_accumulator"],
                    eps=self.row_opt["eps"],
                    g_square_momentum=self.row_opt["g_square_momentum"]),
                schema=schema, worker=self.worker,
                embedding_config=EmbeddingConfig(),
                device_cache_capacity=env.sizes["cache_rows"],
                seed=env.seed % 2147483647)
            self._ctx.__enter__()
            env.mark("services up, ctx entered")
            # the tower's state, built by the program's own function, with
            # the benchmark's leaves in place of its initial values
            self.paths = _tower_paths(cfg)
            self.specs = [s for s in weights.leaf_specs(cfg, [1] * self.tables)
                          if s[2] != "table"]
            self.leaf_index = {
                n: i for i, (n, _, _) in enumerate(
                    weights.leaf_specs(cfg, [1] * self.tables))}
            sample = env.stream.batch(0)
            state = create_train_state(
                model, optimizer, jax.random.key(0),
                [jax.numpy.asarray(sample["dense"])],
                [np.zeros((env.batch, self.dim), np.float32)] * self.tables)
            mine = self._make_tower()
            params = jax.tree_util.tree_map(lambda x: x, state.params)
            for n, p in self.paths.items():
                if _get(params, p).shape != mine[n].shape:
                    raise RuntimeError(f"leaf {n}: program "
                                       f"{_get(params, p).shape}")
                _set(params, p, mine[n])
            self._ctx.state = state.replace(params=params)
            # the compared steps' rows, made by the benchmark, on the PS
            n_check = env.sizes["check_steps"]
            self._check = [env.stream.batch(i) for i in range(n_check)]
            self._ids = _unique_ids(self._check, self.tables)
            signs = np.concatenate(
                [(ids + self.offsets[t]).astype(np.uint64)
                 for t, ids in enumerate(self._ids)])
            vals = np.concatenate(
                [weights.hashed_rows(env.seed, t, ids, self.dim)
                 for t, ids in enumerate(self._ids)])
            acc = np.full_like(vals, self.row_opt["initial_accumulator"])
            self.worker.set_rows(signs, np.concatenate([vals, acc], axis=1),
                                 self.dim)
            if self.check_services() < len(signs):
                raise RuntimeError("the servers hold fewer rows than were "
                                   "put there")
            env.mark("tower state and the compared rows put in")
        except BaseException:
            self.close()
            raise
        self._registry = default_registry()
        self.program = {}
        self._compiles_seen, self._quiet_steps = 0, 0

    def _make_tower(self):
        """The benchmark's tower leaves from the seed (made again after
        steps 1 and 3, to read what the program did to them)."""
        if self._tower_fn is None:
            specs, index = self.specs, self.leaf_index

            def build(key):
                return {n: weights.gen_leaf(key, index[n], shape, kind)
                        for n, shape, kind in specs}

            self._tower_fn = self._jax.jit(build)
        return self._tower_fn(weights.seed_key(self.env.seed))

    # --- feed -----------------------------------------------------------

    def convert(self, b):
        """Generator thread: the program's batch type, signs in disjoint
        per-table ranges."""
        batch_t, id_t, dense_t, label_t = self._batch_types
        signs = (b["ids"] + self.offsets[None, :]).astype(np.uint64)
        return batch_t(
            [id_t(n, np.ascontiguousarray(signs[:, t]))
             for t, n in enumerate(self.names)],
            non_id_type_features=[dense_t(b["dense"])],
            labels=[label_t(b["label"])], requires_grad=True,
            batch_id=b["index"])

    def step(self, feed):
        loss, _ = self._ctx.train_step(feed)
        return loss

    def settled(self):
        """No new shape for a stretch of steps: the padded miss bucket has
        stopped changing (every new bucket is a compilation)."""
        seen = self.env.meter.compiles
        if seen != self._compiles_seen:
            self._compiles_seen, self._quiet_steps = seen, 0
        else:
            self._quiet_steps += 1
        return self._quiet_steps >= self.env.sizes["quiet_steps"]

    # --- what `correct` needs from the timed path ------------------------

    def _rows_now(self, ids_per_table):
        """(values, accumulators) per table as the servers hold them after
        a flush of the cache."""
        self._ctx.flush_device_cache()
        out = []
        for t, ids in enumerate(ids_per_table):
            signs = (ids + self.offsets[t]).astype(np.uint64)
            out.append(self.worker.lookup_rows_with_state(
                signs, self.dim,
                default_state=self.row_opt["initial_accumulator"]))
        return out

    def _tower_now(self):
        state = self._ctx.state
        params = {n: np.asarray(_get(state.params, p))
                  for n, p in self.paths.items()}
        acc = {n: np.asarray(_get(state.opt_state[0].sum_of_squares, p))
               for n, p in self.paths.items()}
        return params, acc

    def after_step(self, k, last):
        if k not in (1, last):
            return
        tower0 = {n: np.asarray(v) for n, v in self._make_tower().items()}
        params, acc = self._tower_now()
        if k == 1:
            # g = (p0 - p1) sqrt(acc + eps) / lr: the tower's Adagrad uses
            # the accumulator after the step, the rows' the one before it
            # (the initial value), and neither accumulator resolves g^2
            lr, eps = self.opt["lr"], self.opt["eps"]
            norms = {n: float(np.linalg.norm(
                (tower0[n] - params[n]) * np.sqrt(acc[n] + eps) / lr))
                for n in params}
            ids1 = _unique_ids(self._check[:1], self.tables)
            lr, eps = self.row_opt["lr"], self.row_opt["eps"]
            scale = np.sqrt(self.row_opt["initial_accumulator"] + eps) / lr
            for t, (vals, _) in enumerate(self._rows_now(ids1)):
                p0 = weights.hashed_rows(self.env.seed, t, ids1[t], self.dim)
                norms[f"table.{t}"] = float(
                    np.linalg.norm((p0 - vals) * scale))
            self.program["grad_norm"] = norms
        if k == last:
            norms = {n: float(np.linalg.norm(params[n] - tower0[n]))
                     for n in params}
            for t, (vals, _) in enumerate(self._rows_now(self._ids)):
                p0 = weights.hashed_rows(self.env.seed, t, self._ids[t],
                                         self.dim)
                norms[f"table.{t}"] = float(np.linalg.norm(vals - p0))
            self.program["change_norm"] = norms

    def counters(self):
        """The program's own cache counters, summed over their labels."""
        names = ("device_cache_hits_total", "device_cache_misses_total",
                 "device_cache_evictions_total")
        out = dict.fromkeys(names, 0.0)
        for line in self._registry.render().splitlines():
            name = line.split("{")[0].split(" ")[0]
            if name in out:
                out[name] += float(line.rsplit(" ", 1)[1])
        return out

    def table_shapes(self):
        rows = self.env.sizes["cache_rows"] + 1
        return [(rows, self.dim)]

    def row_ids(self, b):
        return b["ids"]

    def check_services(self):
        """Native store on every PS, rows present."""
        rows = 0
        for t in self._svc.fleet_targets():
            with urllib.request.urlopen(
                    f"http://{t['http_addr']}/healthz", timeout=10) as r:
                doc = json.load(r)
            if doc.get("backend") != "NativeEmbeddingHolder":
                raise RuntimeError(f"PS store is {doc.get('backend')}")
            rows += doc["holder_entries"]
        return rows

    def close(self):
        try:
            if self._ctx is not None:
                eng = self._ctx._cache_engine
                if eng is not None:
                    # leaving the ctx writes every cached row back to the
                    # servers (28 s for 4 M rows, my chip run, PR 24);
                    # nothing reads them after the window, so drop them
                    eng.invalidate()
                self._ctx.__exit__(None, None, None)
                if eng is not None:
                    eng.cache_vals.delete()
                    eng.cache_acc.delete()
                self._ctx.state = None
                self._ctx = None
            if getattr(self, "worker", None) is not None:
                self.worker.close()
                self.worker = None
        finally:
            if self._svc is not None:
                self._svc.__exit__(None, None, None)
                self._svc = None


def build(env):
    return Runner(env)


def reference_side(env, batches, precision="float32", fault=None):
    """The plain reference: one row per id, rows by ``hashed_rows``, the
    rows' Adagrad with the accumulator from before the step."""
    import jax

    cfg = env.config
    tables = len(cfg["table_cardinalities"])
    ids = _unique_ids(batches, tables)
    local = [np.stack([np.searchsorted(ids[t], b["ids"][:, t])
                       for t in range(tables)], axis=1).astype(np.int32)
             for b in batches]
    pad = sum(len(b["label"]) for b in batches)
    sub = [weights.hashed_rows(env.seed, t, _padded(ids[t], pad),
                               cfg["embedding_dim"]) for t in range(tables)]
    all_specs = weights.leaf_specs(cfg, [1] * tables)
    key = weights.seed_key(env.seed)

    def build_tower(key):
        return {n: weights.gen_leaf(key, i, shape, kind)
                for i, (n, shape, kind) in enumerate(all_specs)
                if kind != "table"}

    mlp = jax.jit(build_tower)(key)
    return reference.first_steps(cfg, cfg["dense_optimizer"],
                                 env.cell["row_optimizer"], mlp, sub, local,
                                 batches, precision=precision, fault=fault)
