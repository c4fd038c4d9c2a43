"""The trace reduction, on records taken from a chip's trace and on a
hand-made one."""
import json
import pathlib

import numpy as np
import pytest

from bench.harness import trace

DATA = pathlib.Path(__file__).resolve().parents[1] / "data"


def _timeline(rec):
    """Busy and Pallas time by painting a 1 us timeline (independent of
    the interval arithmetic under test)."""
    w0, w1 = rec["window_ns"]
    n = (w1 - w0) // 1000 + 1
    busy = np.zeros(n, bool)
    pallas = 0
    for _, s, d, p in rec["device"]:
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        busy[(a - w0) // 1000:(b - w0 + 999) // 1000] = True
        pallas += b - a if p else 0
    return busy.sum() * 1e-6, pallas * 1e-9


@pytest.mark.parametrize("name", ["trace_train_v5e.json",
                                  "trace_infer_v5e.json"])
def test_reduce_recorded_trace(name):
    rec = json.loads((DATA / name).read_text())
    red = trace.reduce(rec)
    busy, pallas = _timeline(rec)
    assert red["window_s"] == pytest.approx(0.06)
    # the painted timeline rounds each interval out to whole microseconds
    assert red["busy_s"] <= busy + 1e-9
    assert red["busy_s"] == pytest.approx(busy, abs=3e-6 * red["ops"])
    assert red["pallas_s"] == pytest.approx(pallas, rel=1e-12)
    assert 0 < red["pallas_s"] <= red["busy_s"] <= red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    tops = [v for _, v in red["device_ops"]]
    assert tops == sorted(tops, reverse=True) and len(tops) <= 10


def test_reduce_hand_made_record():
    rec = {"window_ns": [1000, 11000],
           "device": [["a", 0, 2000, True],        # clipped to 1000..2000
                      ["b", 1500, 1000, False],    # overlaps a
                      ["a", 5000, 1000, True],
                      ["c", 10500, 5000, False]],  # clipped to 10500..11000
           "host": [["bench.step", 2600, 2000], ["bench.mutate", 6000, 500]]}
    red = trace.reduce(rec)
    assert red["window_s"] == pytest.approx(1e-5)
    assert red["busy_s"] == pytest.approx((1500 + 1000 + 500) * 1e-9)
    assert red["pallas_s"] == pytest.approx(2000e-9)
    assert red["ops"] == 4
    assert dict(red["device_ops"]) == pytest.approx(
        {"a": 2e-6, "b": 1e-6, "c": 0.5e-6})
    # gaps: 2500..5000 (bench.step covers 2600..4600), 6000..10500
    # (bench.mutate covers 6000..6500)
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.step": 2000e-9, "bench.mutate": 500e-9,
         "no bench span": (500 + 4000) * 1e-9})


def test_op_names_and_pallas_calls():
    hlo = ('%shard_spmm.2 = f32[20,1024,504]{2,1,0} custom-call(f32[20,20,'
           '1024,1024]{3,2,1,0} %blocks.1), custom_call_target="tpu_custom_'
           'call"')
    assert trace.op_name(hlo) == "shard_spmm.2"
    assert trace.is_pallas(hlo)
    assert not trace.is_pallas("%fusion.50 = bf16[20,1024,500,1] fusion()")
