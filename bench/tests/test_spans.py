"""Nested span reduction and the readers of the program's spans, on a
hand-made trace and on the small recorded one."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness
from bench.spans import reduce_spans
from bench.trace import reduce_events

DEV, HOST = "/device:TPU:0", "/host:CPU"

NESTED = [
    (HOST, "python3", "bench.window", 0, 100),
    (HOST, "python3", "bench.feed", 0, 40),
    (HOST, "python3", "repro.serve.guard", 5, 10),       # 5-15
    (HOST, "python3", "repro.serve.guard", 20, 10),      # 20-30
    (HOST, "python3", "bench.pump", 40, 60),
    (HOST, "python3", "repro.serve.pack", 42, 8),        # 42-50
    (HOST, "python3", "repro.serve.decode", 50, 5),      # 50-55
    (HOST, "python3", "repro.serve.launch", 55, 15),     # 55-70
    (HOST, "python3", "repro.serve.harvest", 80, 15),    # 80-95
    (HOST, "python3", "other.span", 0, 100),             # not kept
    (DEV, "XLA Ops", "%k.1 = f32[8] custom-call(...)", 12, 8),    # 12-20
    (DEV, "XLA Ops", "%copy.2 = f32[8] copy(...)", 60, 15),       # 60-75
]

SESSIONS_READERS = {
    "guard_us_per_feed.sessions": 20e-9 / 2 * 1e6,
    "pack_ms_per_tile.sessions": 8e-9 / 4 * 1e3,
    "decode_ms_per_tile.sessions": 5e-9 / 4 * 1e3,
    "launch_ms_per_tile.sessions": 15e-9 / 4 * 1e3,
    "harvest_ms_per_tile.sessions": 15e-9 / 4 * 1e3,
}
TRAIN_READERS = ("decode_ms_per_commit.train", "learner_ms_per_commit.train")


def _recorded():
    data = json.loads((Path(__file__).parent / "trace_cue_train.json")
                      .read_text())
    return [tuple(e) for e in data["events"]]


def test_self_time_and_innermost_gaps_by_hand():
    s = reduce_spans(NESTED)
    assert s.span_s["bench.feed"] == pytest.approx(40e-9)
    assert s.self_s["bench.feed"] == pytest.approx(20e-9)
    assert s.self_s["bench.pump"] == pytest.approx((60 - 8 - 5 - 15 - 15) * 1e-9)
    assert s.span_n["repro.serve.guard"] == 2
    assert s.self_s["repro.serve.guard"] == pytest.approx(20e-9)
    assert "other.span" not in s.span_s
    # gaps: 0-12 (midpoint 6, in a guard), 20-60 (40: the pump, outside
    # any program span), 75-100 (87.5: the harvest)
    assert s.idle_gaps == pytest.approx({"repro.serve.guard": 12e-9,
                                         "bench.pump": 40e-9,
                                         "repro.serve.harvest": 25e-9})
    # Rolled up to the harness's spans, the gaps are bench/trace.py's, and
    # with the busy time they still fill the window.
    flat = reduce_events(NESTED)
    assert flat.idle_gaps == pytest.approx({"bench.feed": 12e-9,
                                            "bench.pump": 65e-9})
    assert flat.busy_s + sum(s.idle_gaps.values()) == pytest.approx(
        flat.window_s)


def test_spans_clip_to_the_window():
    ev = [(HOST, "t", "bench.window", 10, 80),
          (HOST, "t", "repro.learn.commit", 0, 20),          # 10-20 inside
          (HOST, "t", "repro.learn.commit", 85, 30)]         # 85-90 inside
    s = reduce_spans(ev)
    assert s.span_s["repro.learn.commit"] == pytest.approx(15e-9)
    assert s.span_n["repro.learn.commit"] == 2


def test_recorded_trace_without_program_spans_reads_as_before():
    events = _recorded()
    s, flat = reduce_spans(events), reduce_events(events)
    assert s.idle_gaps == pytest.approx(flat.idle_gaps)
    assert not any(n.startswith("repro.") for n in s.span_s)
    assert s.span_s["bench.window"] == pytest.approx(flat.window_s)


def test_readers_of_program_spans():
    run = SimpleNamespace(trace=1, spans=reduce_spans(NESTED),
                          stats={"feed_calls": 2, "tiles": 4})
    for name, want in SESSIONS_READERS.items():
        assert harness.reader(name)(run) == pytest.approx(want), name
    train = SimpleNamespace(trace=1, stats={"commits": 2}, spans=reduce_spans(
        [(HOST, "t", "bench.window", 0, 100),
         (HOST, "t", "bench.commit", 0, 50),
         (HOST, "t", "repro.data.decode", 0, 10),
         (HOST, "t", "repro.learn.commit", 10, 30)]))
    assert harness.reader(TRAIN_READERS[0])(train) == pytest.approx(5e-6)
    assert harness.reader(TRAIN_READERS[1])(train) == pytest.approx(15e-6)


@pytest.mark.parametrize("trace", [1, 0])
def test_readers_read_nothing_without_program_spans(trace):
    """A trace with no ``repro.*`` spans (a program without them), or an
    untraced run, gives ``None``."""
    run = SimpleNamespace(trace=trace, spans=reduce_spans(_recorded()),
                          stats={"feed_calls": 2, "tiles": 4, "commits": 2})
    for name in (*SESSIONS_READERS, *TRAIN_READERS):
        assert harness.reader(name)(run) is None, name
