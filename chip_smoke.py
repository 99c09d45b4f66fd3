#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still runs on the chip.

One process drives the DLRM training path once through the entry points a
user calls, at the full MLPerf/Criteo-terabyte DLRM width
(``DLRM(embedding_dim=128, bottom_mlp=(512, 256),
top_mlp=(1024, 1024, 512, 256))``, 26 slots of dim 128, 13 dense features,
batch 4096, zipf ids from ``persia_tpu.workloads.generator``):

- ``hybrid``  ServiceCtx (real PS + worker subprocesses over TCP) ->
  TrainCtx -> DataLoader(embedding_staleness=8) -> train_step, then an
  eval forward on a held-out batch;
- ``cached``  TrainCtx(device_cache_capacity=...) over the same PS
  subprocesses, with the embedding worker in the trainer's process (the
  cache engine needs ``lookup_rows_with_state``/``set_rows``, which
  RemoteEmbeddingWorker does not carry);
- ``device``  DeviceModeModel + make_device_mode_trainer, tables plus
  whole-table Adagrad state filling about half of HBM;
- ``kernel``  the Pallas attention kernel: forward and backward against
  ``reference_attention`` at T=4096 and a ragged T=1000 (dh 128, bf16),
  then a masked ragged-history SequenceTower(attn_impl="pallas") step
  whose compiled text must hold the Mosaic custom call;
- with four or more devices: hybrid on a (4, 1) mesh, cached on (4, 1),
  device mode on (2, 2), the kernel and the sequence tower through
  Ulysses + Pallas on (1, 4) — each with per-device memory printed.

It needs a TPU: with any other platform it exits 2 before it runs
anything and prints no result. It chooses no platform, no interpret
mode and no reference path by itself; a phase that fails raises, so
the exit code is non-zero and neither closing line is printed. The
last line of standard output is the result, one JSON object with
exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The line before it, ``chip_smoke: summary {...}``, carries what each
phase saw; the timings in it are information for whoever reads the log,
not metrics.

``--tiny`` is the builder's rehearsal on whatever platform is there
(the sandbox CPU): sizes cut to seconds, output labelled with the real
platform. The driver never passes it. There is no phase selector: a
summary line always means every phase this machine can run has passed
(to debug one phase, call its ``run_*_phase`` from a REPL).
"""

import argparse
import dataclasses
import gc
import json
import os
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

EMB_DIM, NUM_SLOTS, NUM_DENSE = 128, 26, 13
BOTTOM_MLP, TOP_MLP = (512, 256), (1024, 1024, 512, 256)


@dataclasses.dataclass(frozen=True)
class Sizes:
    batch: int = 4096
    steps: int = 25              # the first compiles; >= 20 steady after it
    vocab_scale: float = 10.0    # CriteoSpec vocabs 1e3 .. 2e6, log-spread
    cache_rows: int = 1 << 18    # x 128 x f32 x (value + accumulator) = 268 MB
    table_rows: int = 1 << 18    # x 26 x 128 x f32 = 3.5 GB, x2 with Adagrad
    attn_lengths: tuple = (4096, 1000)
    attn_batch: int = 4
    attn_heads: int = 4          # slot dim 512 -> dh 128


TINY = Sizes(batch=256, steps=8, vocab_scale=0.05, cache_rows=4096,
             table_rows=1024, attn_lengths=(256, 100), attn_batch=2,
             attn_heads=4)


def log(msg):
    print(msg, flush=True)


def require(ok, msg):
    """A failed check ends the run (not ``assert``: -O must not skip it)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


# --- compile accounting ---------------------------------------------------

class CompileMeter:
    """The program's own compile watch (``tracing.compile_watch()``:
    JAX's backend-compile durations, persistent-cache loads included,
    and its persistent-cache hits), read per phase."""

    def __init__(self):
        from persia_tpu import tracing

        self._watch = tracing.compile_watch()

    seconds = property(lambda self: self._watch.seconds)
    cache_hits = property(lambda self: self._watch.cache_hits)

    def mark(self):
        w = self._watch
        return w.seconds, w.compiles, w.cache_hits

    def since(self, mark):
        now = self.mark()
        return {"compile_s": round(now[0] - mark[0], 2),
                "compiles": now[1] - mark[1],
                "cache_hits": now[2] - mark[2]}


def device_memory(jax):
    """Per-device (bytes_in_use, peak_bytes_in_use). The TPU backend
    reports them; the CPU one (--tiny rehearsal) does not: None."""
    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats is None:
            if d.platform == "tpu":
                raise RuntimeError(f"{d} reports no memory_stats()")
            return None
        out.append((int(stats["bytes_in_use"]),
                    int(stats["peak_bytes_in_use"])))
    return out


def check_spread(name, jax):
    """A mesh phase must not leave everything on one device."""
    mem = device_memory(jax)
    if mem is None:
        log(f"[{name}] per-device memory: not reported by this backend")
        return None
    in_use = [m[0] for m in mem]
    log(f"[{name}] per-device bytes_in_use: {in_use}")
    require(all(b > 0 for b in in_use),
            f"{name}: a device holds nothing: {in_use}")
    require(max(in_use) < 0.9 * sum(in_use),
            f"{name}: one device holds everything: {in_use}")
    return in_use


def loss_summary(name, losses, need_fall: bool):
    import numpy as np

    losses = [float(x) for x in losses]
    require(all(np.isfinite(losses)),
            f"{name}: non-finite loss {losses}")
    head, tail = float(np.mean(losses[:5])), float(np.mean(losses[-5:]))
    log(f"[{name}] loss first5 {head:.4f} last5 {tail:.4f} "
        f"(step0 {losses[0]:.4f}, last {losses[-1]:.4f})")
    if need_fall:
        require(tail < head, f"{name}: loss did not fall: first5 "
                f"{head:.4f} last5 {tail:.4f}")
    return {"steps": len(losses), "first_loss": round(losses[0], 4),
            "last_loss": round(losses[-1], 4),
            "first5": round(head, 4), "last5": round(tail, 4)}


# --- the DLRM stack -------------------------------------------------------

def criteo_spec(sz: Sizes):
    from persia_tpu.workloads.generator import CriteoSpec

    return dataclasses.replace(
        CriteoSpec.build(scale=sz.vocab_scale, alpha=1.05),
        dims=(EMB_DIM,) * NUM_SLOTS)


def dlrm():
    from persia_tpu.models import DLRM

    return DLRM(embedding_dim=EMB_DIM, bottom_mlp=BOTTOM_MLP,
                top_mlp=TOP_MLP)


def build_native():
    """Build the native library from the committed sources on THIS
    machine (the Makefile compiles with -march=native; a library built
    elsewhere must not travel) before anything loads it."""
    t0 = time.perf_counter()
    subprocess.run(["make", "-C", os.path.join(REPO, "native"), "-B",
                    "build/libpersia_native.so"], check=True,
                   stdout=subprocess.DEVNULL)
    built = os.path.join(REPO, "native", "build", "libpersia_native.so")
    from persia_tpu.ps.native import load_native_lib
    from persia_tpu.worker import mw_native

    lib = load_native_lib(build_if_missing=False)
    require(lib is not None and os.path.samefile(lib._name, built),
            f"native library loaded from {getattr(lib, '_name', None)}, "
            f"built {built}")
    require(mw_native.available(), "middleware kernels are not native")
    log(f"[native] built + loaded {built} in "
        f"{time.perf_counter() - t0:.1f}s")


def check_services(name, svc, n_ps, n_workers):
    """What the PS and worker subprocesses say about themselves, from
    each one's /healthz sidecar: native store, native middleware, rows."""
    from persia_tpu.service.coordinator import ROLE_PS, ROLE_WORKER

    docs = {ROLE_PS: [], ROLE_WORKER: []}
    for t in svc.fleet_targets():
        with urllib.request.urlopen(
                f"http://{t['http_addr']}/healthz", timeout=10) as r:
            docs[t["role"]].append(json.load(r))
    ps, workers = docs[ROLE_PS], docs[ROLE_WORKER]
    require(len(ps) == n_ps and len(workers) == n_workers,
            f"{name}: found {len(ps)} PS / {len(workers)} worker sidecars")
    backends = sorted({d["backend"] for d in ps})
    rows = sum(d["holder_entries"] for d in ps)
    mw = sorted({d["mw_kernels"] for d in workers})
    log(f"[{name}] PS backend {backends} simd "
        f"{sorted({str(d.get('simd')) for d in ps})} rows {rows}; "
        f"worker middleware {mw}")
    require(backends == ["NativeEmbeddingHolder"],
            f"{name}: PS store is not the native one: {backends}")
    # no worker sidecar (cached): the middleware ran in this process,
    # where build_native() already proved the native kernels load
    require(mw == ["native"] * min(n_workers, 1),
            f"{name}: worker middleware is {mw}")
    require(rows > 0, f"{name}: no rows reached the PS")
    return {"ps_rows": rows, "ps_backend": backends[0],
            "mw_kernels": "native"}


def run_sparse_phase(name, jax, meter, sz: Sizes, *, cached: bool,
                     mesh_shape=None, seed=0):
    """hybrid / cached, one chip or a (data, 1) mesh: ServiceCtx ->
    worker -> TrainCtx -> DataLoader -> train_step -> eval. Hybrid talks
    to a worker subprocess through ``svc.remote_worker()``; cached hosts
    the EmbeddingWorker in this process over PsClients to the same PS
    subprocesses — the only topology the cache engine supports."""
    import optax

    from persia_tpu.config import EmbeddingSchema, uniform_slots
    from persia_tpu.ctx import TrainCtx, eval_ctx
    from persia_tpu.data.dataloader import DataLoader, IterableDataset
    from persia_tpu.embedding import EmbeddingConfig
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.parallel.mesh import make_mesh
    from persia_tpu.service.helper import ServiceCtx
    from persia_tpu.service.ps_service import PsClient
    from persia_tpu.worker.worker import EmbeddingWorker
    from persia_tpu.workloads.generator import (
        CRITEO_SLOT_NAMES,
        dlrm_batches,
    )

    mark, t_phase = meter.mark(), time.perf_counter()
    n_workers = 0 if cached else 1
    spec = criteo_spec(sz)
    schema = EmbeddingSchema(
        slots_config=uniform_slots(CRITEO_SLOT_NAMES, dim=EMB_DIM))
    mesh = None
    if mesh_shape is not None:
        mesh = make_mesh(mesh_shape,
                         devices=jax.devices()[:mesh_shape[0] * mesh_shape[1]])
    out = {}
    with ServiceCtx(schema, n_workers=n_workers, n_ps=2,
                    http_all=True) as svc:
        if cached:
            worker = EmbeddingWorker(
                schema, [PsClient(a) for a in svc.ps_addrs])
        else:
            worker = svc.remote_worker()
        ctx = TrainCtx(
            model=dlrm(), dense_optimizer=optax.adagrad(0.02),
            embedding_optimizer=Adagrad(lr=0.05), schema=schema,
            worker=worker, embedding_config=EmbeddingConfig(), mesh=mesh,
            device_cache_capacity=sz.cache_rows if cached else 0, seed=seed)
        losses, t_first = [], None
        with ctx:
            loader = DataLoader(
                IterableDataset(dlrm_batches(
                    sz.steps * sz.batch, sz.batch, seed=seed, spec=spec)),
                num_workers=4, embedding_staleness=8)
            t0 = time.perf_counter()
            for batch in loader:
                loss, _ = ctx.train_step(batch)
                losses.append(loss)
                if t_first is None:
                    jax.block_until_ready(loss)
                    t_first = time.perf_counter() - t0
                    t0 = time.perf_counter()
            jax.block_until_ready(losses[-1])
            t_steady = (time.perf_counter() - t0) / (len(losses) - 1)
            require(len(losses) == sz.steps,
                    f"{name}: {len(losses)} steps ran, wanted {sz.steps}")
            out.update(loss_summary(name, losses, need_fall=True))
            if cached:
                eng = ctx._cache_engine
                mapper = type(eng.mapper).__name__
                n_shards = len({tuple(s.index) for s in
                                eng.cache_vals.addressable_shards})
                flushed = ctx.flush_device_cache()
                log(f"[{name}] hit_rate {eng.hit_rate:.3f} mapper {mapper} "
                    f"cache shards {n_shards} flushed rows {flushed}")
                require(eng.hit_rate > 0, f"{name}: cache never hit")
                require(flushed > 0, f"{name}: flush wrote nothing")
                require(mapper == "NativeSignSlotMap",
                        f"{name}: sign->slot mapper is {mapper}")
                if mesh is not None:
                    require(n_shards == mesh.size,
                            f"{name}: cache in {n_shards} shards on a "
                            f"{mesh.size}-device mesh")
                out.update(hit_rate=round(eng.hit_rate, 3), flushed=flushed,
                           cache_shards=n_shards)
            else:
                # the loader's iterator flushed the backward engine on
                # exhaustion; transport loss is counted, not raised
                lost = loader._engine.backward.lost_updates
                log(f"[{name}] lost_updates {lost}")
                require(lost == 0,
                        f"{name}: {lost} gradient updates lost")
                out["lost_updates"] = lost
                if mesh is not None:
                    require(ctx._ddp,
                            f"{name}: did not take the shard_map DDP step")
                    out["ddp_step"] = True
            if mesh is not None:
                out["bytes_in_use"] = check_spread(name, jax)
        heldout = next(dlrm_batches(sz.batch, sz.batch, seed=seed + 1000,
                                    spec=spec, requires_grad=False))
        with eval_ctx(ctx) as ectx:
            pred, _ = ectx.forward(heldout)
        pred = jax.device_get(pred)
        require(pred.shape == (sz.batch, 1),
                pred.shape)
        require(((pred >= 0) & (pred <= 1)).all(),
                "eval pred outside [0, 1]")
        log(f"[{name}] eval forward ok: pred {pred.shape} "
            f"mean {float(pred.mean()):.4f}")
        out.update(check_services(name, svc, n_ps=2, n_workers=n_workers))
        if cached:
            worker.close()
    out.update(first_step_s=round(t_first, 2),
               steady_step_s=round(t_steady, 4),
               phase_s=round(time.perf_counter() - t_phase, 1),
               **meter.since(mark))
    return out


def run_device_phase(name, jax, meter, sz: Sizes, mesh_shape, seed=0):
    """Device mode: every table in HBM (sharded over the mesh's model
    axis), trained with whole-table optax.adagrad in one jitted step."""
    import jax.numpy as jnp
    import optax

    from persia_tpu.parallel.device_mode import (
        DeviceModeModel,
        make_device_mode_trainer,
    )
    from persia_tpu.parallel.mesh import make_mesh, shard_batch_pytree
    from persia_tpu.workloads.generator import (
        CRITEO_SLOT_NAMES,
        dlrm_batches,
    )

    mark, t_phase = meter.mark(), time.perf_counter()
    n_dev = mesh_shape[0] * mesh_shape[1]
    mesh = make_mesh(mesh_shape, devices=jax.devices()[:n_dev])
    specs = [(n, sz.table_rows, EMB_DIM) for n in CRITEO_SLOT_NAMES]
    model = DeviceModeModel(slot_specs=specs, tower=dlrm())

    def device_batch(b):
        non_id = [jnp.asarray(b.non_id_type_features[0].data)]
        ids = {f.name: jnp.asarray(f.signs.astype("int64") % (1 << 31),
                                   jnp.int32).reshape(-1, 1)
               for f in b.id_type_features}
        label = jnp.asarray(b.labels[0].data)
        placed = shard_batch_pytree({"n": non_id, "i": ids, "l": label},
                                    mesh)
        return placed["n"], placed["i"], placed["l"]

    batches = dlrm_batches(sz.steps * sz.batch, sz.batch, seed=seed,
                           spec=criteo_spec(sz))
    non_id, ids, label = device_batch(next(batches))
    params, opt_state, step = make_device_mode_trainer(
        model, optax.adagrad(0.02), mesh, non_id, ids, seed=seed)
    table = params["DeviceEmbeddingCollection_0"][
        f"bag_{CRITEO_SLOT_NAMES[0]}"]["table"]
    table_shards = len({tuple(s.index) for s in table.addressable_shards})
    log(f"[{name}] mesh {mesh_shape} table {table.shape} in "
        f"{table_shards} distinct shard(s), spec {table.sharding.spec}")
    require(table_shards == mesh_shape[1],
            f"{name}: tables in {table_shards} shards, model axis "
            f"{mesh_shape[1]}")
    losses = []
    with mesh:
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, non_id, ids, label)
        jax.block_until_ready(loss)
        t_first = time.perf_counter() - t0
        losses.append(loss)
        t0 = time.perf_counter()
        for b in batches:
            non_id, ids, label = device_batch(b)
            params, opt_state, loss = step(params, opt_state, non_id, ids,
                                           label)
            losses.append(loss)
        jax.block_until_ready(loss)
        t_steady = (time.perf_counter() - t0) / (len(losses) - 1)
    out = loss_summary(name, losses, need_fall=False)
    mem = device_memory(jax)
    if mem is not None:
        log(f"[{name}] peak_bytes_in_use per device: {[m[1] for m in mem]}")
    if n_dev > 1:
        out["bytes_in_use"] = check_spread(name, jax)
    out.update(table_shards=table_shards, first_step_s=round(t_first, 2),
               steady_step_s=round(t_steady, 4),
               phase_s=round(time.perf_counter() - t_phase, 1),
               **meter.since(mark))
    return out


# --- the attention kernel -------------------------------------------------

def _rel_err(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-6))


def run_kernel_phase(name, jax, meter, sz: Sizes, on_tpu: bool,
                     mesh_shape=None):
    """The Pallas attention kernel, masked and ragged, forward and
    backward. At each length: the bare kernel against
    reference_attention, then a SequenceTower(attn_impl="pallas") grad
    step against the xla implementation with the same parameters. On a
    (1, 4) mesh both go through Ulysses (``ulysses_self_attention``,
    ``context_parallel="ulysses"``)."""
    import jax.numpy as jnp
    import numpy as np

    from persia_tpu.models.seq import SequenceTower
    from persia_tpu.ops.flash_attention import flash_attention_masked
    from persia_tpu.parallel.mesh import make_mesh
    from persia_tpu.parallel.ring_attention import reference_attention
    from persia_tpu.parallel.ulysses import ulysses_self_attention

    mark, t_phase = meter.mark(), time.perf_counter()
    b, h, dh = sz.attn_batch, sz.attn_heads, EMB_DIM
    d = h * dh
    rng = np.random.default_rng(7)
    mesh = None
    if mesh_shape is not None:
        mesh = make_mesh(mesh_shape,
                         devices=jax.devices()[:mesh_shape[0] * mesh_shape[1]])
    out = {"lengths": list(sz.attn_lengths)}

    def ragged_mask(t):
        lengths = rng.integers(t // 4, t + 1, size=b)
        lengths[0] = t  # one full row, the rest ragged
        return jnp.asarray(np.arange(t)[None, :] < lengths[:, None])

    def assert_mosaic(compiled, what):
        has = "tpu_custom_call" in compiled.as_text()
        if on_tpu:
            require(has, f"{name}: no Mosaic custom call in the {what}")
            log(f"[{name}] {what}: Mosaic custom call present")
        else:
            log(f"[{name}] {what}: Mosaic check not applicable off-TPU "
                f"(rehearsal runs the Pallas interpreter)")
        return has

    for t in sz.attn_lengths:
        mask = ragged_mask(t)
        q, k, v = (jnp.asarray(rng.normal(size=(b, h, t, dh)) * 0.5,
                               jnp.bfloat16) for _ in range(3))
        if mesh is None:
            what = "kernel"

            def attn(q, k, v, mask=mask):
                return flash_attention_masked(q, k, v, kv_mask=mask)
        else:
            what = f"ulysses kernel mesh {mesh_shape}"

            def attn(q, k, v, mask=mask):
                return ulysses_self_attention(q, k, v, mesh, kv_mask=mask,
                                              impl="pallas")

        def loss_of(attn):
            def f(q, k, v):
                o = attn(q, k, v)
                return jnp.sum(o.astype(jnp.float32) ** 2), o
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))

        pallas = loss_of(attn).lower(q, k, v).compile()
        assert_mosaic(pallas, f"{what} grad step T={t}")
        ref = loss_of(lambda q, k, v, mask=mask: reference_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), kv_mask=mask))
        (_, o_p), g_p = pallas(q, k, v)
        (_, o_r), g_r = ref(q, k, v)
        errs = {"out": _rel_err(o_p, o_r)}
        for nm, gp, gr in zip(("dq", "dk", "dv"), g_p, g_r):
            errs[nm] = _rel_err(gp, gr)
        log(f"[{name}] T={t} {what} vs reference_attention, max-abs "
            f"error / max-abs value: "
            + " ".join(f"{k_}={v_:.4f}" for k_, v_ in errs.items()))
        require(all(np.isfinite(e) and e < 3e-2 for e in errs.values()),
                f"{name}: T={t} {what} disagrees with the reference: "
                f"{errs}")
        out[f"T{t}_kernel_err"] = {k_: round(v_, 4)
                                   for k_, v_ in errs.items()}

        # the tower: one masked ragged-history raw slot of dim h*dh
        index = jnp.asarray(
            np.where(np.asarray(mask),
                     rng.integers(1, b * t, size=(b, t)), 0), jnp.int32)
        emb = jnp.asarray(rng.normal(size=(b * t + 1, d)) * 0.5,
                          jnp.float32).at[0].set(0.0)
        non_id = [jnp.asarray(rng.normal(size=(b, NUM_DENSE)), jnp.float32)]
        label = jnp.asarray(rng.integers(0, 2, size=(b, 1)), jnp.float32)
        kw = dict(num_heads=h, mlp=(256, 128))
        if mesh is not None:
            kw.update(mesh=mesh, context_parallel="ulysses")
        tower = SequenceTower(attn_impl="pallas", **kw)
        tower_ref = SequenceTower(attn_impl="xla", **kw)
        variables = tower.init(jax.random.key(0), non_id, [(emb, index)])

        def step_of(model):
            def f(variables, emb):
                pred = model.apply(variables, non_id, [(emb, index)],
                                   train=True)
                pred = jnp.clip(pred, 1e-7, 1 - 1e-7)
                return -jnp.mean(label * jnp.log(pred)
                                 + (1 - label) * jnp.log(1 - pred))
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

        t0 = time.perf_counter()
        compiled = step_of(tower).lower(variables, emb).compile()
        assert_mosaic(compiled, f"SequenceTower grad step T={t}")
        loss_p, (gv_p, ge_p) = compiled(variables, emb)
        jax.block_until_ready(ge_p)
        t_first = time.perf_counter() - t0
        loss_r, (gv_r, ge_r) = step_of(tower_ref)(variables, emb)
        # single gradient elements can differ by their whole value (a
        # bf16 rounding flips a ReLU unit), so the gradients are compared
        # as one vector. Bounds are ~10x what the v5e gave (loss error
        # 1e-5..4e-5, gradient L2 error 0.0005..0.0009: CHANGES.md PR 21)
        leaves_p = jax.tree_util.tree_leaves((gv_p, ge_p))
        leaves_r = jax.tree_util.tree_leaves((gv_r, ge_r))
        require(all(bool(jnp.isfinite(x).all()) for x in leaves_p),
                f"{name}: T={t} non-finite gradient")
        diff2 = sum(float(jnp.sum((a.astype(jnp.float32) - b_) ** 2))
                    for a, b_ in zip(leaves_p, leaves_r))
        ref2 = sum(float(jnp.sum(b_.astype(jnp.float32) ** 2))
                   for b_ in leaves_r)
        gerr = (diff2 / ref2) ** 0.5
        lerr = abs(float(loss_p) - float(loss_r))
        tag = "tower" if mesh is None else f"ulysses tower mesh {mesh_shape}"
        log(f"[{name}] T={t} {tag}: loss pallas {float(loss_p):.5f} "
            f"xla {float(loss_r):.5f}, gradient relative L2 error "
            f"{gerr:.4f} over {len(leaves_r)} leaves, compile+first step "
            f"{t_first:.1f}s")
        require(np.isfinite(float(loss_p)) and lerr < 1e-3 and gerr < 2e-2,
                f"{name}: T={t} {tag} disagrees: loss {lerr} grads {gerr}")
        out[f"T{t}_tower"] = {"loss": round(float(loss_p), 5),
                              "loss_err": round(lerr, 5),
                              "grad_err": round(gerr, 4)}
    if mesh is not None:
        out["bytes_in_use"] = check_spread(name, jax)
    out.update(phase_s=round(time.perf_counter() - t_phase, 1),
               **meter.since(mark))
    return out


# --- main -----------------------------------------------------------------

def result_line(device):
    """The last line of standard output: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``), nothing else — the driver
    refuses any other shape. Details go on the summary line before it."""
    require(sorted(device) == ["count", "kind", "platform"], device)
    return json.dumps({"ok": True, "device": device})


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tiny", action="store_true",
                   help="builder's rehearsal: tiny sizes, any platform")
    args = p.parse_args()

    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"chip_smoke: platform: {device['platform']} device_kind: "
        f"{device['kind']} devices: {device['count']} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {libtpu}")
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: needs a TPU, found platform "
              f"{device['platform']!r}; nothing was run", file=sys.stderr)
        return 2
    sz = TINY if args.tiny else Sizes()

    sys.path.insert(0, REPO)
    from persia_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    require(cache_dir, "no compile cache: persia_tpu was imported from "
            "outside this checkout and JAX_COMPILATION_CACHE_DIR is unset")
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    log(f"chip_smoke: compile cache at {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if from_env else 'default'})")
    meter = CompileMeter()
    build_native()

    n = len(devices)
    phases = {
        "hybrid": lambda: run_sparse_phase(
            "hybrid", jax, meter, sz, cached=False, seed=0),
        "cached": lambda: run_sparse_phase(
            "cached", jax, meter, sz, cached=True, seed=1),
        "device": lambda: run_device_phase(
            "device", jax, meter, sz, (1, 1), seed=2),
        "kernel": lambda: run_kernel_phase("kernel", jax, meter, sz, on_tpu),
        "hybrid_4x1": lambda: run_sparse_phase(
            "hybrid_4x1", jax, meter, sz, cached=False, mesh_shape=(4, 1),
            seed=3),
        "cached_4x1": lambda: run_sparse_phase(
            "cached_4x1", jax, meter, sz, cached=True, mesh_shape=(4, 1),
            seed=4),
        "device_2x2": lambda: run_device_phase(
            "device_2x2", jax, meter, sz, (2, 2), seed=5),
        "ulysses_1x4": lambda: run_kernel_phase(
            "ulysses_1x4", jax, meter, sz, on_tpu, mesh_shape=(1, 4)),
    }
    results, not_run = {}, {}
    t_all = time.perf_counter()
    for name, run in phases.items():
        if name.endswith(("_4x1", "_2x2", "_1x4")) and n < 4:
            not_run[name] = f"needs 4 devices, found {n}"
        else:
            log(f"=== phase {name} ===")
            results[name] = run()  # a failing phase raises: no summary
            gc.collect()
            mem = device_memory(jax)
            if mem is not None:  # the process's high-water mark so far
                results[name]["peak_bytes_in_use"] = max(m[1] for m in mem)
            log(f"[{name}] {json.dumps(results[name])}")
    for name, why in not_run.items():
        log(f"chip_smoke: phase {name} did not run: {why}")
    summary = {
        "device": device,
        "tiny": bool(args.tiny),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache_dir": cache_dir,
        "compile_s_total": round(meter.seconds, 2),
        "cache_hits_total": meter.cache_hits,
        "wall_s": round(time.perf_counter() - t_all, 1),
        "phases": results,
        "not_run": not_run,
        "claim": None,
    }
    log(f"chip_smoke: summary {json.dumps(summary)}")
    log(result_line(device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
