"""Checkpoint, resharding, incremental update, metrics, k8s gen tests."""

import os

import numpy as np
import pytest

from persia_tpu.checkpoint import (
    dump_sharded,
    iter_psd_entries,
    load_sharded,
    read_done_marker,
)
from persia_tpu.inc_update import IncrementalUpdateDumper, IncrementalUpdateLoader
from persia_tpu.metrics import MetricsRegistry
from persia_tpu.ps.store import EmbeddingHolder


def _holders(n, seed_entries=0):
    out = []
    for i in range(n):
        h = EmbeddingHolder(capacity=10_000, num_internal_shards=2)
        h.configure("bounded_uniform", {"lower": -0.1, "upper": 0.1})
        h.register_optimizer({"type": "sgd", "lr": 0.1, "wd": 0.0})
        out.append(h)
    return out


def _route_and_fill(holders, num_signs=200, dim=4):
    """Populate holders the way the worker routes: farmhash % n."""
    from persia_tpu.hashing import sign_to_shard

    signs = np.arange(1, num_signs + 1, dtype=np.uint64)
    shards = sign_to_shard(signs, len(holders))
    for i, h in enumerate(holders):
        h.lookup(signs[shards == i], dim, training=True)
    return signs


def test_dump_load_same_shard_count(tmp_path):
    holders = _holders(2)
    signs = _route_and_fill(holders, 100)
    dump_sharded(holders, str(tmp_path))
    assert read_done_marker(str(tmp_path))["num_shards"] == 2

    fresh = _holders(2)
    load_sharded(fresh, str(tmp_path))
    for a, b in zip(holders, fresh):
        assert len(a) == len(b)
    # entry-level equality
    for s in signs[:20]:
        src = next(h.get_entry(int(s)) for h in holders
                   if h.get_entry(int(s)) is not None)
        dst = next(h.get_entry(int(s)) for h in fresh
                   if h.get_entry(int(s)) is not None)
        np.testing.assert_array_equal(src[1], dst[1])


def test_reshard_2_to_3(tmp_path):
    from persia_tpu.hashing import sign_to_shard

    holders = _holders(2)
    signs = _route_and_fill(holders, 300)
    dump_sharded(holders, str(tmp_path))

    fresh = _holders(3)
    load_sharded(fresh, str(tmp_path))
    assert sum(len(h) for h in fresh) == 300
    # every entry must live on the shard the worker would route to
    shards = sign_to_shard(signs, 3)
    for s, shard in zip(signs[:50], shards[:50]):
        assert fresh[shard].get_entry(int(s)) is not None
        for other in range(3):
            if other != shard:
                assert fresh[other].get_entry(int(s)) is None


def test_iter_psd_entries_streams_all(tmp_path):
    (h,) = _holders(1)
    h.lookup(np.arange(10, dtype=np.uint64), 4, training=True)
    path = str(tmp_path / "x.psd")
    h.dump_file(path)
    entries = list(iter_psd_entries(path))
    assert len(entries) == 10
    assert all(dim == 4 and len(vec) == 4 for _, dim, vec in entries)


def test_incremental_update_roundtrip(tmp_path):
    (train_h,) = _holders(1)
    signs = np.arange(1, 50, dtype=np.uint64)
    train_h.lookup(signs, 4, training=True)
    train_h.update_gradients(signs, np.ones((49, 4), np.float32), 4)

    dumper = IncrementalUpdateDumper(train_h, str(tmp_path / "inc"),
                                     buffer_size=10)
    dumper.commit(signs)  # over buffer size -> auto flush
    dumper.flush()

    (infer_h,) = _holders(1)
    loader = IncrementalUpdateLoader(infer_h, str(tmp_path / "inc"))
    loaded = loader.scan_once()
    assert loaded == 49
    for s in signs[:5]:
        np.testing.assert_array_equal(infer_h.get_entry(int(s))[1],
                                      train_h.get_entry(int(s))[1])
    # idempotent: second scan loads nothing new
    assert loader.scan_once() == 0
    # a dumper SIGKILLed mid-packet leaves a .tmp directory whose
    # marker may exist and be empty: a restore must pass over it
    torn = tmp_path / "inc" / "inc_29991231235959_000001_r0_p1.tmp"
    torn.mkdir()
    (torn / "inc_update_done").write_text("")
    (torn / "0.inc").write_bytes(b"")
    assert loader.scan_once() == 0


def test_concurrent_flushes_replay_in_the_order_their_rows_were_read(
        tmp_path):
    """Packets replay in name order and hold the rows as they read at
    dump time. With several update handlers flushing at once, a packet
    named later must never carry an OLDER row than one named earlier,
    or a restore ends on the old value and an acked update is lost.
    Counting arm: a row only ever decreases."""
    import threading

    h = EmbeddingHolder(capacity=10_000, num_internal_shards=2)
    h.configure("bounded_uniform", {"lower": 0.0, "upper": 0.0},
                1.0, 1e9, False)
    h.register_optimizer({"type": "sgd", "lr": 1.0, "wd": 0.0})
    inc = str(tmp_path / "inc")
    dumper = IncrementalUpdateDumper(h, inc, buffer_size=1)
    signs = np.arange(1, 33, dtype=np.uint64)
    h.lookup(signs, 4, training=True)

    def handler():
        for _ in range(100):
            h.update_gradients(signs, np.ones((32, 4), np.float32), 4)
            dumper.commit(signs)

    threads = [threading.Thread(target=handler) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    replayed = {}
    for name in sorted(os.listdir(inc)):
        for sign, _d, vec in iter_psd_entries(
                os.path.join(inc, name, "0.inc")):
            assert vec[0] <= replayed.get(sign, 0.0), (name, sign)
            replayed[sign] = vec[0]
    for s in signs:
        assert replayed[int(s)] == h.get_entry(int(s))[1][0] == -400.0


def test_metrics_registry_render():
    reg = MetricsRegistry(const_labels={"instance": "test-0"})
    reg.counter("lookups_total").inc(3)
    reg.gauge("staleness", {"worker": "0"}).set(2)
    h = reg.histogram("lookup_seconds")
    h.observe(0.003)
    h.observe(0.2)
    text = reg.render()
    assert 'lookups_total{instance="test-0"} 3.0' in text
    assert 'staleness{instance="test-0",worker="0"} 2' in text
    assert "lookup_seconds_count" in text
    assert "lookup_seconds_sum" in text
    with pytest.raises(ValueError):
        reg.gauge("lookups_total")  # kind conflict


def test_k8s_manifest_generation(tmp_path):
    import yaml

    from persia_tpu.k8s_utils import gen_manifests

    spec = {
        "jobName": "demo",
        "image": "persia-tpu:latest",
        "embeddingConfigPath": "/cfg/emb.yml",
        "roles": {
            "embeddingParameterServer": {"replicas": 2},
            "embeddingWorker": {"replicas": 1},
            "nnWorker": {"replicas": 1, "entry": "train.py",
                         "tpu": {"type": "tpu-v5p-slice", "chips": 4}},
            "dataloader": {"replicas": 1, "entry": "load.py"},
        },
    }
    manifests = gen_manifests(spec)
    kinds = [m["kind"] for m in manifests]
    assert kinds.count("Service") == 1
    assert kinds.count("Pod") == 1 + 2 + 1 + 1 + 1  # coordinator + roles
    ps0 = next(m for m in manifests
               if m["metadata"]["name"] == "demo-embeddingparameterserver-0")
    env = {e["name"]: e["value"] for e in
           ps0["spec"]["containers"][0]["env"]}
    assert env["REPLICA_INDEX"] == "0"
    assert env["REPLICA_SIZE"] == "2"
    assert env["PERSIA_COORDINATOR_ADDR"] == "demo-coordinator:23333"
    nn = next(m for m in manifests
              if m["metadata"]["name"] == "demo-nnworker-0")
    assert "google.com/tpu" in \
        nn["spec"]["containers"][0]["resources"]["limits"]
    yaml.safe_dump_all(manifests)  # serializable


def test_ctx_checkpoint_dense_and_sparse(tmp_path):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "examples" / "adult_income"))
    import train as adult_income
    from data_generator import batches

    ctx = adult_income.build_ctx(seed=13)
    with ctx:
        for b in batches(4 * 64, 64, seed=17):
            ctx.train_step(b)
        ctx.dump_checkpoint(str(tmp_path / "ckpt"))
        step_before = int(ctx.state.step)

        # keep training, then restore
        for b in batches(2 * 64, 64, seed=18):
            ctx.train_step(b)
        assert int(ctx.state.step) == step_before + 2
        ctx.load_checkpoint(str(tmp_path / "ckpt"))
        assert int(ctx.state.step) == step_before
    assert os.path.exists(tmp_path / "ckpt" / "embedding_dump_done")


def test_dense_checkpoint_roundtrip_via_state_template(tmp_path):
    """serving.load_dense_state must rebuild the exact trained dense
    state from checkpoint bytes using only (model, schema, num_dense) —
    the serving CLI's boot path."""
    import jax
    import optax
    from flax import serialization

    from persia_tpu.config import EmbeddingSchema, uniform_slots
    from persia_tpu.models import DNN
    from persia_tpu.parallel.train import create_train_state
    from persia_tpu.serving import load_dense_state

    schema = EmbeddingSchema(slots_config=uniform_slots(["a", "b"], dim=8))
    model = DNN()
    num_dense = 5
    non_id = [np.random.default_rng(0).normal(size=(1, num_dense))
              .astype(np.float32)]
    emb_inputs = [np.ones((1, 8), np.float32), np.ones((1, 8), np.float32)]
    # adam, like the examples: its opt_state pytree differs from the
    # serving template's, which load_dense_state must tolerate (serving
    # never uses optimizer state)
    state = create_train_state(model, optax.adam(1e-3), jax.random.key(3),
                               non_id, emb_inputs)
    path = tmp_path / "dense.msgpack"
    path.write_bytes(serialization.to_bytes(state))
    restored = load_dense_state(model, schema, num_dense, str(path))
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(restored.step) == int(state.step)
