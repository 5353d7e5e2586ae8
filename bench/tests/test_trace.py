"""The trace reduction, on a hand-made trace and on a small recorded one."""

import json
from pathlib import Path

import pytest

from bench.trace import reduce_events

DEV, HOST = "/device:TPU:0", "/host:CPU"


def test_busy_union_op_totals_and_gap_attribution_by_hand():
    ev = [
        (HOST, "python3", "bench.window", 0, 100),
        (HOST, "python3", "bench.feed", 0, 40),
        (HOST, "python3", "bench.pump", 40, 60),
        (DEV, "XLA Ops", "%k.1 = f32[8] custom-call(...)", 10, 20),   # 10-30
        (DEV, "XLA Ops", "%copy.2 = f32[8] copy(...)", 25, 10),      # 25-35
        (DEV, "XLA Ops", "%k.1 = f32[8] custom-call(...)", 70, 10),  # 70-80
        (DEV, "XLA Ops", "%late", 95, 20),                           # 95-100 in
        (DEV, "XLA Modules", "jit__scatter(1)", 25, 10),
    ]
    s = reduce_events(ev)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((25 + 10 + 5) * 1e-9)
    assert s.op_s["%k.1"] == pytest.approx(30e-9)
    assert s.op_seconds("copy") == pytest.approx(10e-9)
    assert s.module_seconds("_scatter") == pytest.approx(10e-9)
    # gaps: 0-10 feed, 35-70 pump (midpoint 52.5), 80-95 pump
    assert s.idle_gaps == pytest.approx({"bench.feed": 10e-9,
                                         "bench.pump": 50e-9})


def test_recorded_chip_trace():
    data = json.loads((Path(__file__).parent / "trace_cue_train.json")
                      .read_text())
    s = reduce_events(tuple(e) for e in data["events"])
    assert s.window_s == pytest.approx(0.25)
    assert s.busy_s == pytest.approx(0.001409038, rel=1e-6)
    assert s.top_ops(1)[0][0] == "%rsnn_train.1"
    assert s.op_seconds("rsnn_train") == pytest.approx(0.000799153, rel=1e-5)
    assert set(s.idle_gaps) == {"bench.commit"}
    assert s.busy_s + sum(s.idle_gaps.values()) == pytest.approx(s.window_s)
    assert s.module_seconds("train_batch") > 0
