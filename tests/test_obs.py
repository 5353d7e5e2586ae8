"""The program's spans and timestamped counters (``repro.obs``): off unless
a profiler collects or ``obs.enable(True)``; when on, the engine's guard,
pack, decode, launch and harvest, the pipeline's decode and the learner's
commit show in the profiler's trace; the packer's queue wait and the tile
latency percentiles read from the engine's stream stats."""

import glob

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import aer
from repro.core.controller import ControllerConfig, OnlineLearner
from repro.core.rsnn import Presets, init_params
from repro.data.braille import BrailleConfig, make_braille_dataset
from repro.data.pipeline import make_pipeline
from repro.optim.eprop_opt import EpropSGDConfig
from repro.serve import BatchedEngine

SERVE_SPANS = ("guard", "pack", "decode", "launch", "harvest")


@pytest.fixture(autouse=True)
def _obs_off():
    obs.enable(False)
    yield
    obs.enable(False)


def _request(rng, n_in, ticks, label=1):
    raster = (rng.random((ticks, n_in)) < 0.25).astype(np.float32)
    ev = aer.encode_sample(
        raster, label, label_tick=max(0, ticks // 4), end_tick=ticks - 1
    )
    ev = np.asarray(ev, np.uint32)
    return ev[np.argsort(ev & aer.MAX_TICK, kind="stable")]


def _engine(n=3, T=32, **kw):
    cfg = Presets.braille(n_classes=3, num_ticks=T)
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [_request(rng, cfg.n_in, T, label=i % 3) for i in range(n)]
    eng = BatchedEngine(cfg, params, backend="scan", max_batch=4, **kw)
    return eng, reqs


def _events(log_dir):
    """``{name: [(line, start_ns, end_ns), ...]}`` of the host spans."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (line.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_off_makes_no_annotation(monkeypatch):
    """Off and with no profiler collecting, span() is one shared null
    context and the engine creates no annotation on its feed and pump."""
    def refuse(*a, **kw):
        raise AssertionError("TraceAnnotation created while obs is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not obs.enabled()
    assert obs.span("serve.guard") is obs.span("serve.pack")
    eng, reqs = _engine(n=1)
    h = eng.open_session()
    h.feed(reqs[0])
    eng.pump(drain=True)
    assert h.poll() is not None and h.poll().ticks > 0


def test_serve_spans_nest_in_the_pump(tmp_path):
    """Under a profiler trace the engine's five spans are recorded, and
    pack, decode and launch lie inside an annotation around pump()."""
    eng, reqs = _engine(n=2, tick_tile=8)
    h = [eng.open_session() for _ in reqs]
    h[0].feed(reqs[0])
    eng.pump(drain=True)                    # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        assert obs.enabled()
        h[1].feed(reqs[1])
        with jax.profiler.TraceAnnotation("test.pump"):
            eng.pump(drain=True)
    assert not obs.enabled()
    ev = _events(tmp_path)
    for name in SERVE_SPANS:
        assert f"repro.serve.{name}" in ev, name
    (line, a, b), = ev["test.pump"]
    for name in ("pack", "decode", "launch"):
        for ln, s, e in ev[f"repro.serve.{name}"]:
            assert ln == line and a <= s <= e <= b, name


def test_learner_and_pipeline_spans(tmp_path):
    """One END_B commit over an ARM pipeline shows the pipeline's decode and
    the learner's commit."""
    T = 32
    data = make_braille_dataset(
        "AEU", BrailleConfig(num_ticks=T, samples_per_class=2))
    cfg = Presets.braille(n_classes=3, num_ticks=T)
    learner = OnlineLearner(
        cfg, ControllerConfig(commit="batch", samples_per_batch=6),
        EpropSGDConfig(lr=0.01, clip=10.0), jax.random.key(0), backend="scan",
    )
    pipe = make_pipeline("arm", data, samples_per_batch=6)
    learner.train_batch(next(pipe.batches("train", 0)))      # compile
    with jax.profiler.trace(str(tmp_path)):
        learner.train_batch(next(pipe.batches("train", 1)))
        jax.block_until_ready(learner.weights)
    ev = _events(tmp_path)
    assert len(ev["repro.learn.commit"]) == 1
    assert len(ev["repro.data.decode"]) >= 1


@pytest.mark.parametrize("on", [True, False])
def test_pack_wait_on_the_engine_clock(on):
    """A session queued at t=0 and packed at t=5 waited 5 s; with obs off
    nothing is stamped and the stat reads None."""
    now = [0.0]
    eng, reqs = _engine(n=3, clock=lambda: now[0])
    obs.enable(on)
    eng.reset_stream_stats()
    handles = [eng.open_session() for _ in reqs]
    for h, ev in zip(handles, reqs):
        h.feed(ev)
    now[0] = 5.0
    eng.pump(drain=True)
    stats = eng.stream_stats(wall_s=5.0)
    assert stats.tiles > 0
    if on:
        assert stats.p95_pack_wait_s == pytest.approx(5.0)
    else:
        assert stats.p95_pack_wait_s is None
        assert all(h._sess.t_queued is None for h in handles)


def test_tile_latency_p95_between_p50_and_p99():
    eng, reqs = _engine(n=3, tick_tile=8)
    eng.reset_stream_stats()
    for ev in reqs:
        eng.open_session().feed(ev)
    eng.pump(drain=True)
    stats = eng.stream_stats(wall_s=1.0)
    assert stats.tiles > 1
    assert (stats.p50_tile_latency_s <= stats.p95_tile_latency_s
            <= stats.p99_tile_latency_s)
