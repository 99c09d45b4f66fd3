"""Criteo example: format parsing + end-to-end training smoke
(the reference workload's entry point)."""

import importlib.util
import pathlib
import sys

import numpy as np

EX = pathlib.Path(__file__).resolve().parent.parent / "examples" / "criteo"
sys.path.insert(0, str(EX))

from criteo_data import (  # noqa: E402
    NUM_DENSE,
    NUM_SLOTS,
    criteo_batches,
    synthetic_batches,
    write_synthetic_tsv,
)


def _load_criteo_train():
    """Load examples/criteo/train.py under a unique module name: the
    adult-income example also has a `train` module, and whichever test
    imports first would otherwise win via sys.modules."""
    spec = importlib.util.spec_from_file_location(
        "criteo_train", EX / "train.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tsv_parsing_roundtrip(tmp_path):
    path = tmp_path / "day_0.tsv"
    write_synthetic_tsv(str(path), 300, seed=4)
    batches = list(criteo_batches(str(path), batch_size=128))
    assert [len(b.labels[0].data) for b in batches] == [128, 128, 44]
    b = batches[0]
    assert len(b.id_type_features) == NUM_SLOTS
    dense = b.non_id_type_features[0].data
    assert dense.shape == (128, NUM_DENSE)
    assert (dense >= 0).all()  # log1p of clamped ints
    signs = b.id_type_features[0].data
    # missing tokens -> sign 0; present tokens never 0
    assert signs[0].dtype == np.uint64


def test_max_samples_caps_stream(tmp_path):
    path = tmp_path / "t.tsv"
    write_synthetic_tsv(str(path), 100, seed=1)
    got = sum(len(b.labels[0].data)
              for b in criteo_batches(str(path), 32, max_samples=50))
    assert got == 50


def test_criteo_training_smoke(tmp_path):
    """Real-format file through the full hybrid path (tiny)."""
    criteo_train = _load_criteo_train()

    path = tmp_path / "train.tsv"
    write_synthetic_tsv(str(path), 600, seed=7)
    args = __import__("argparse").Namespace(
        train=str(path), test=None, synthetic=False, local=True,
        embedding_config="/nonexistent", num_remote_workers=1,
        model="dlrm", dim=8, batch_size=128, samples=600,
        test_samples=256, vocab=1 << 12, n_ps=2, ps_capacity=100_000,
        ps_shards=4, lr=0.05, sparse_lr=0.05, staleness=4, num_workers=2,
        mesh=None, grad_reduce_dtype=None, seed=0, log_every=100,
    )
    # test=None: evaluation falls back to a slice of the train file
    auc = criteo_train.main(args)
    assert np.isfinite(auc)


def test_synthetic_batches_shape():
    bs = list(synthetic_batches(300, 128, seed=2))
    assert [len(b.labels[0].data) for b in bs] == [128, 128, 44]
    assert all(len(b.id_type_features) == NUM_SLOTS for b in bs)


def test_example_uses_shared_workloads_generator():
    """The example's synthetic streams ARE the workload zoo's (one
    shared definition for tests, benches and examples), and the shared
    stream is deterministic per seed."""
    from persia_tpu.workloads import generator as zoo

    assert synthetic_batches is zoo.criteo_uniform_batches
    from criteo_data import learnable_batches

    assert learnable_batches is zoo.criteo_learnable_batches
    a = next(iter(synthetic_batches(64, 64, seed=5)))
    b = next(iter(zoo.criteo_uniform_batches(64, 64, seed=5)))
    assert a.to_bytes() == b.to_bytes()


def test_example_training_smoke_zoo_model(tmp_path):
    """The zoo's mixed-dim tower (zoo-dlrm) through the example's full
    hybrid path — the shared generator + shared model combination."""
    criteo_train = _load_criteo_train()

    path = tmp_path / "train.tsv"
    write_synthetic_tsv(str(path), 400, seed=11)
    args = __import__("argparse").Namespace(
        train=str(path), test=None, synthetic=False, local=True,
        embedding_config="/nonexistent", num_remote_workers=1,
        model="zoo-dlrm", dim=8, batch_size=128, samples=400,
        test_samples=128, vocab=1 << 12, n_ps=2, ps_capacity=100_000,
        ps_shards=4, lr=0.05, sparse_lr=0.05, staleness=4, num_workers=2,
        mesh=None, grad_reduce_dtype=None, seed=0, log_every=100,
    )
    auc = criteo_train.main(args)
    assert np.isfinite(auc)


def test_non_hex_tokens_do_not_crash(tmp_path):
    """Corrupt/non-hex categorical tokens fall back to raw-byte packing
    instead of aborting the stream mid-epoch."""
    path = tmp_path / "odd.tsv"
    row = ["1"] + ["5"] * NUM_DENSE + (
        ["deadbeef"] * (NUM_SLOTS - 2) + ["not-hex!", "x" * 40])
    path.write_text("\t".join(row) + "\n")
    (b,) = list(criteo_batches(str(path), 8))
    signs = np.stack([f.signs for f in b.id_type_features], axis=1)
    assert signs.shape == (1, NUM_SLOTS)
    assert (signs != 0).all()  # every present token got a sign


def test_replica_sharding_splits_stream_without_overlap(tmp_path):
    path = tmp_path / "t.tsv"
    write_synthetic_tsv(str(path), 400, seed=3)
    full = [b for b in criteo_batches(str(path), 64)]
    r0 = list(criteo_batches(str(path), 64, replica_index=0,
                             replica_size=2))
    r1 = list(criteo_batches(str(path), 64, replica_index=1,
                             replica_size=2))
    n_full = sum(len(b.labels[0].data) for b in full)
    n0 = sum(len(b.labels[0].data) for b in r0)
    n1 = sum(len(b.labels[0].data) for b in r1)
    assert n0 + n1 == n_full == 400
    # no overlap: sign streams are disjoint slices of the full stream
    s_full = np.concatenate([b.id_type_features[0].signs for b in full])
    s0 = np.concatenate([b.id_type_features[0].signs for b in r0])
    s1 = np.concatenate([b.id_type_features[0].signs for b in r1])
    assert len(s0) + len(s1) == len(s_full)
    np.testing.assert_array_equal(np.sort(np.concatenate([s0, s1])),
                                  np.sort(s_full))
