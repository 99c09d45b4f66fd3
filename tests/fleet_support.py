"""Shared pieces of the fleet-level tests (kill matrices, trainer
groups, live reshards): the counting arm and its identity, postmortem
bundle validation, a bounded poll, a per-test time limit, and the zoo
scenario stack. Everything here asserts counts, bytes and recoveries —
never a rate."""

import contextlib
import json
import os
import signal
import time

import numpy as np

from persia_tpu.service.trainer_service import (
    ARM_INIT,
    ARM_OPT,
    batch_draws,
)

# zero init + unit-lr plain SGD + unit gradients: a row's value is
# exactly -(number of updates it absorbed), elementwise — every
# "zero lost updates" assertion reads off this identity
COUNTING_ARM = (ARM_INIT, ARM_OPT)


@contextlib.contextmanager
def time_limit(seconds, what="test"):
    """Fail the calling test after ``seconds`` instead of hanging the
    suite: SIGALRM raises in the main thread (where pytest runs the
    test), so the test's own ``finally`` blocks still tear the fleet
    down. Blocking joins, subprocess waits and socket reads all return
    when the handler raises."""
    def fire(_sig, _frm):
        raise TimeoutError(f"{what} exceeded its {seconds}s limit")

    old = signal.signal(signal.SIGALRM, fire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def wait_until(cond, timeout, what, interval=0.05):
    """Poll ``cond`` until it is truthy; fail with ``what`` on timeout.
    Returns the seconds waited."""
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"{what} (waited {timeout}s)")
        time.sleep(interval)
    return time.monotonic() - t0


def arm_counting(target):
    """Arm a PsClient, a holder or a worker with the counting optimizer."""
    configure = getattr(target, "configure_parameter_servers", None)
    (configure or target.configure)(*ARM_INIT)
    target.register_optimizer(ARM_OPT)


def unit_update(worker, feats):
    """One lookup + unit-gradient update cycle through ``worker``."""
    ref, out = worker.lookup_direct_training(feats)
    worker.update_gradients(
        ref, {k: np.ones_like(v.embeddings) for k, v in out.items()})


def expected_counts(pool, seed, steps, bs, n_feats, start=0):
    """Regenerate the trainer driver's deterministic stream and return
    the per-sign expected update counts for steps [start, steps)."""
    expected = np.zeros(len(pool), np.int64)
    for k in range(start, steps):
        draws = batch_draws(pool, seed, k, bs, n_feats)
        np.add.at(expected,
                  np.searchsorted(pool, np.concatenate(draws)), 1)
    return expected


def applied_counts(worker, pool, dim):
    """Per-sign applied update counts read back through the worker."""
    rows = worker.lookup_signs(pool, dim)
    return -rows.sum(axis=1) / dim


def assert_counting_identity(tag, pool, expected, got, tol=1e-3):
    bad = np.nonzero(np.abs(got - expected) > tol)[0]
    forensic = [{"sign": int(pool[i]), "expected": int(expected[i]),
                 "got": round(float(got[i]), 2)} for i in bad[:8]]
    assert not len(bad), (
        f"[{tag}] counting identity broken on {len(bad)} signs "
        f"(expected {int(expected.sum())} total updates, applied "
        f"{got.sum():.1f}); first: {forensic}")


def owner_filtered_applied(holders, table, dim):
    """Counting identity over in-process per-entry holders: sum of
    -row values over rows AT THEIR OWNERS under ``table`` (donors keep
    stale frozen copies of moved rows through the double-read window by
    design — those must not double-count)."""
    applied = 0.0
    for i, h in enumerate(holders):
        rows = [(s, -float(vec[:d].sum()) / dim)
                for shard in h._shards
                for s, (d, vec) in shard._map.items()]
        if not rows:
            continue
        owners = table.replica_of(np.array([s for s, _ in rows],
                                           np.uint64))
        applied += sum(v for (_s, v), o in zip(rows, owners) if o == i)
    return applied


def validate_postmortem(bundle_dir, health_key="model_manager_status"):
    """A crash postmortem bundle must hold a VALID Chrome trace (at
    least one intact parent->child chain on one trace_id, no orphan
    parents — remote parents were promoted at capture), the final
    health doc, and a parseable last metrics snapshot.

    ``health_key`` is the field that proves the health doc is the real
    tier-specific one (PS and trainer docs carry
    ``model_manager_status``; worker docs ``forward_buffer_depth``)."""
    from persia_tpu.metrics import parse_exposition

    assert bundle_dir and os.path.isdir(bundle_dir), \
        f"no postmortem bundle at {bundle_dir!r}"
    with open(os.path.join(bundle_dir, "trace.json")) as f:
        trace = json.load(f)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert xs, f"postmortem trace in {bundle_dir} is empty"
    ids = {e["args"]["span_id"] for e in xs}
    orphans = [e["name"] for e in xs
               if e["args"].get("parent_id")
               and e["args"]["parent_id"] not in ids]
    assert not orphans, f"postmortem trace has orphan parents: {orphans}"
    children = [e for e in xs if e["args"].get("parent_id")]
    assert children, "postmortem trace has no parent->child chain"
    tid = children[0]["args"]["trace_id"]
    chain = [e for e in xs if e["args"]["trace_id"] == tid]
    assert len(chain) >= 2, f"trace_id {tid} is not a chain"
    with open(os.path.join(bundle_dir, "health.json")) as f:
        health = json.load(f)
    assert health_key in health, \
        f"final health doc incomplete (no {health_key!r}): {health}"
    with open(os.path.join(bundle_dir, "metrics.prom")) as f:
        samples, _families = parse_exposition(f.read())
    assert samples, "last metrics snapshot is empty"
    return health


def scenario_stack(scenario, n_ps=2, hotness=False, resume_from=None):
    """One in-process hybrid stack (holders + worker + ctx) for a zoo
    scenario, with the zoo's calibrated optimizer pair (adam dense,
    Adagrad(0.1) sparse) that every scenario's AUC floor was tuned
    against. ``resume_from`` hands the ctx a job snapshot to roll the
    (fresh, empty) stack back onto."""
    import optax

    from persia_tpu.ctx import TrainCtx
    from persia_tpu.embedding import EmbeddingConfig
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.ps.native import make_holder
    from persia_tpu.worker.worker import EmbeddingWorker

    holders = [make_holder(2_000_000, 8, hotness=hotness)
               for _ in range(n_ps)]
    worker = EmbeddingWorker(scenario.schema, holders)
    ctx = TrainCtx(
        model=scenario.model(),
        dense_optimizer=optax.adam(2e-3),
        embedding_optimizer=Adagrad(lr=0.1),
        schema=scenario.schema,
        worker=worker,
        embedding_config=EmbeddingConfig(emb_initialization=(-0.05, 0.05)),
        loss_fn=scenario.loss_fn,
        seed=scenario.seed,
        resume_from=resume_from,
    )
    return ctx, worker, holders
