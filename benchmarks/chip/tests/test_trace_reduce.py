"""The reduction from a trace to numbers: exact arithmetic on a trace
small enough to work out by hand, and the same code over two steps
recorded on a v5e (``recorded_trace.json.gz``, cut from a run of the
mlperf device cell by ``tools/describe_trace.py``)."""

import gzip
import json
import os

import pytest

import describe_trace
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE = "%fusion.7 = f32[100,16]{1,0} fusion(f32[100,16]{1,0} %p), kind=kLoop"


def by_hand():
    ns = 1.0
    return {"devices": [{"name": "/device:TPU:0", "ops": [
        ["%convolution.3 = bf16[8,8]{1,0} convolution(...)", 0 * ns, 10 * ns],
        [TABLE, 10 * ns, 20 * ns],
        ["%fusion.9 = f32[8]{0} fusion(...), kind=kInput", 12 * ns, 3 * ns],
        ["%convolution.4 = bf16[8,8]{1,0} convolution(...)", 50 * ns,
         10 * ns]],
        "modules": [["jit_step(1)", 0.0, 30.0], ["jit_step(1)", 50.0, 10.0],
                    ["jit_other(2)", 31.0, 1.0]]}],
        "spans": [["data_wait", 28.0, 17.0], ["train_call", 45.0, 7.0]]}


def test_reduce_by_hand():
    r = trace_reduce.reduce(by_hand(), [(100, 16)])
    # busy: [0,30) and [50,60) = 40 ns of a 60 ns window; the nested
    # 3 ns operation inside the table fusion is counted once
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["window_s"] == pytest.approx(60e-9)
    # main module: runs of 30 and 10 ns, median 20: two steps' worth
    assert r["steps"] == pytest.approx(2.0)
    assert r["table_s"] == pytest.approx(20e-9)
    ops = dict(map(tuple, r["ops"]))
    assert ops["fusion:kLoop [tables]"] == pytest.approx(20e-9)
    assert ops["convolution"] == pytest.approx(20e-9)
    assert ops["fusion:kInput"] == pytest.approx(3e-9)
    # the one gap, [30,50): data_wait covers 15 of its 20 ns
    assert r["gaps"] == [["data_wait", pytest.approx(20e-9)]]
    b = trace_reduce.breakdown(r)
    assert b["idle_gaps"][0] == ["data_wait.total", pytest.approx(20e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_gap_nobody_covers_is_other():
    t = by_hand()
    t["spans"] = [["train_call", 30.0, 4.0]]
    assert trace_reduce.reduce(t)["gaps"][0][0] == "other"


def test_no_device_operation_reduces_to_nothing():
    assert trace_reduce.reduce({"devices": [], "spans": []}) is None
    assert trace_reduce.reduce(
        {"devices": [{"name": "d", "ops": [], "modules": []}],
         "spans": []}) is None


def test_trim_keeps_whole_steps():
    t = by_hand()
    t["devices"][0]["modules"] = [["jit_step(1)", 0.0, 5.0],
                                  ["jit_step(1)", 10.0, 20.0],
                                  ["jit_step(1)", 50.0, 10.0]]
    cut = describe_trace.trim(t, steps=2)
    assert [m[1] for m in cut["devices"][0]["modules"]] == [10.0, 50.0]
    assert all(10.0 <= e[1] and e[1] + e[2] <= 60.0
               for e in cut["devices"][0]["ops"])


def test_recorded_v5e_trace():
    path = os.path.join(HERE, "recorded_trace.json.gz")
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    r = trace_reduce.reduce(doc["trace"], doc["table_shapes"])
    want = doc["expect"]
    assert r["steps"] == pytest.approx(want["steps"], rel=1e-6)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["table_s"] == pytest.approx(want["table_s"], rel=1e-9)
    assert r["busy_s"] <= r["window_s"]
    # device mode at MLPerf widths: the tables' work is most of the step
    assert r["table_s"] > 0.8 * r["busy_s"]
    assert r["ops"][0][0].endswith("[tables]")
