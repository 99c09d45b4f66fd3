"""The yardstick's arithmetic against values worked out by hand, and the
traffic generator's promises."""

import hashlib
import json
import os

import numpy as np
import pytest

import check
import costs
import reference
import traffic
import weights

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,macs", [
    # 13*512 + 512*256 + 256*128 = 170496; 351 pairs * 128 = 44928;
    # 479*1024 + 1024*1024 + 1024*512 + 512*256 + 256 = 2194688
    ("dlrm-mlperf", 170496 + 44928 + 2194688),
    # 13*512 + 512*256 + 256*64 + 64*16 = 155136; 351 * 16 = 5616;
    # 367*512 + 512*256 + 256 = 319232
    ("dlrm-kaggle", 155136 + 5616 + 319232),
])
def test_tower_macs_by_hand(name, macs):
    cfg = config(name)
    assert costs.tower_macs_per_sample(cfg) == macs
    assert costs.train_flops_per_sample(cfg) == 6 * macs


def test_mac_totals_are_the_issue_s():
    assert costs.tower_macs_per_sample(config("dlrm-mlperf")) == 2410112
    assert costs.tower_macs_per_sample(config("dlrm-kaggle")) == 479984


@pytest.mark.parametrize("name,row_bytes", [("dlrm-mlperf", 512),
                                            ("dlrm-kaggle", 64)])
def test_embed_min_bytes_by_hand(name, row_bytes):
    cfg = config(name)
    # 1000 distinct rows: value + accumulator, read + written; a batch of
    # 4096 x 26 pooled rows written and their gradient read, in bfloat16
    want = 1000 * row_bytes * 4 + 2 * 4096 * 26 * (row_bytes // 4) * 2
    assert costs.embed_min_bytes(1000, 4096, cfg) == want


def test_unique_rows_counts_per_table():
    rows = np.array([[1, 5], [1, 6], [2, 5]])
    assert costs.unique_rows(rows) == 2 + 2


@pytest.mark.parametrize("name,rows,total", [
    ("dlrm-mlperf", None, 7401902), ("dlrm-kaggle", None, 33762577)])
def test_rows_held(name, rows, total):
    assert sum(weights.table_rows(config(name), rows)) == total
    # a mesh's model axis: every table splits evenly
    assert all(r % 2 == 0 for r in
               weights.table_rows(config(name), rows, multiple_of=2))


def test_unknown_device_kind_is_an_error():
    assert costs.peaks_for(BENCH_DIR, "TPU v5 lite")[
        "bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks_for(BENCH_DIR, "cpu")


def test_stream_is_a_function_of_seed_and_index():
    mix = traffic.load_mix(os.path.join(BENCH_DIR, "mixes", "zipf.json"))
    cards = config("dlrm-kaggle")["table_cardinalities"]
    a = traffic.Stream(mix, cards, 13, 512, 2**31 + 11)
    b = traffic.Stream(mix, cards, 13, 512, 2**31 + 11)
    c = traffic.Stream(mix, cards, 13, 512, 5)
    for key in ("ids", "dense", "label"):
        assert np.array_equal(a.batch(7)[key], b.batch(7)[key])
    assert not np.array_equal(a.batch(7)["ids"], a.batch(8)["ids"])
    assert not np.array_equal(a.batch(7)["ids"], c.batch(7)["ids"])
    ids = a.batch(0)["ids"]
    assert ids.min() >= 0 and (ids.max(axis=0) < np.array(cards)).all()
    pre = traffic.Prefetcher(a, 3, 4)
    try:
        got = [pre.get()["index"] for _ in range(9)]
    finally:
        pre.close()
    assert got == list(range(9))


@pytest.mark.parametrize("index,sha256", [
    (0, "a76e28559b52bb503490d6e3df4da186acf5b62c0b65b4c0e617940a268e4032"),
    (7, "e2082af3735f4042cf11a424afbd37c284c8d392badfc187f38fe4b07516e9d2"),
])
def test_the_accepted_cells_stream_is_pinned(index, sha256):
    """``dlrm-kaggle`` under ``mixes/zipf.json``, batch 512, seed
    2^31 + 11, through the harness's own way in: the bytes of ``ids``,
    ``dense`` and ``label`` as PR 26's tree drew them (checksums taken
    there), so a change to the generator that moves what the accepted
    cells draw shows here."""
    mix = traffic.load_mix(os.path.join(BENCH_DIR, "mixes", "zipf.json"))
    b = traffic.stream_for(mix, config("dlrm-kaggle"), 512,
                           2**31 + 11).batch(index)
    h = hashlib.sha256()
    for key in ("ids", "dense", "label"):
        h.update(np.ascontiguousarray(b[key]).tobytes())
    assert (b["ids"].dtype, b["dense"].dtype, b["label"].dtype) == (
        np.int64, np.float32, np.float32)
    assert h.hexdigest() == sha256


def test_a_mix_names_its_generator(tmp_path):
    """A mix of another ``kind`` is taken when ``generators/<kind>.py``
    lies beside ``mixes/``, drawn by that file's ``stream``, and refused
    with the path looked for when it does not."""
    (tmp_path / "mixes").mkdir()
    mix_file = tmp_path / "mixes" / "ramp.json"
    mix_file.write_text(json.dumps({"name": "ramp", "kind": "ramp",
                                    "start": 5}))
    want = str(tmp_path / "generators" / "ramp.py")
    with pytest.raises(ValueError) as e:
        traffic.load_mix(str(mix_file))
    assert "'ramp'" in str(e.value) and want in str(e.value)
    (tmp_path / "generators").mkdir()
    (tmp_path / "generators" / "ramp.py").write_text(
        "import types\n\nimport numpy as np\n\n\n"
        "def stream(mix, config, batch, seed):\n"
        "    def make(i):\n"
        "        lo = mix['start'] + config['step'] * i\n"
        "        return {'index': i, 'x': np.arange(lo, lo + batch)}\n\n"
        "    return types.SimpleNamespace(batch=make)\n")
    mix = traffic.load_mix(str(mix_file))
    assert mix["generator"] == want and mix["start"] == 5
    b = traffic.stream_for(mix, {"step": 10}, 4, 1).batch(2)
    assert b["index"] == 2 and list(b["x"]) == [25, 26, 27, 28]
    # the generator in this file still checks its own law
    mix_file.write_text(json.dumps({"kind": "dlrm_stream", "id_law": "x"}))
    with pytest.raises(ValueError, match="id_law"):
        traffic.load_mix(str(mix_file))


@pytest.mark.parametrize("alpha", [1.05, 0.0])
def test_rank_law_matches_the_exact_cdf(alpha):
    """Head and tail together give the truncated zipf (alpha 0: the
    uniform law): against the exact inverse CDF over a vocabulary small
    enough to hold."""
    vocab = 200_000
    law = traffic.RankLaw(vocab, alpha, head=1024)
    exact = traffic.RankLaw(vocab, alpha, head=vocab)
    assert abs((law.head_mass + law.tail_mass) / exact.head_mass - 1) < 1e-6
    u = np.random.default_rng(0)
    a = law.ranks(u, 200_000)
    b = exact.ranks(np.random.default_rng(0), 200_000)
    # the same uniforms fall on the same rank, up to a neighbour at a
    # boundary
    assert np.abs(a - b).max() <= 1
    assert (a != b).mean() < 1e-3
    assert a.max() < vocab and a.min() == 0


def test_row_rules():
    ids = np.array([0, 1, 2, 3, 10])
    assert list(reference.row_index(ids, 4, "hashed")) == [2, 3, 1, 2, 3]
    assert list(reference.row_index(ids, 4, "exact")) == list(ids)


def test_compare_measures_the_gap_of_norms_at_the_worst_leaf():
    ref = {"losses": [0.7, 0.69, 0.68],
           "grad_norm": {"a": 1.0, "b": 2.0, "c": 1e-9},
           "change_norm": {"a": 0.1, "b": 0.2, "c": 0.5}}
    prog = {"losses": [0.7, 0.69, 0.6868],
            "grad_norm": {"a": 1.1, "b": 2.0, "c": 0.05},
            "change_norm": {"a": 0.1, "b": 0.25, "c": 9.0}}
    numbers, where = check.compare(prog, ref)
    assert numbers["loss_gap"] == pytest.approx(0.01)
    assert where["loss_gap"] == "step 3"
    # c's own norm is nought: it is held against the median leaf's (1.0)
    assert numbers["grad_gap"] == pytest.approx(0.1)
    assert where["grad_gap"] == "a"
    # c's reference gradient is under a thousandth of the median: left out
    assert numbers["change_gap"] == pytest.approx(0.05 / 0.2)
    assert where["change_gap"] == "b"
    ok, compared = check.judge(numbers, {"loss_gap": 0.02, "grad_gap": 0.05})
    assert not ok and compared["grad_gap"]["limit"] == 0.05
    assert check.judge(numbers, {"loss_gap": 0.02, "grad_gap": 0.2})[0]
    assert not check.judge(numbers, {"loss_gap": 0.02}, False)[0]
