"""The ``lsnn_cue`` configuration and the two cells this benchmark adds
(``lsnn_cue.train``, ``braille_q.serve``): they resolve by name, the
configuration file builds the program's preset, their readers read a
synthetic trace, each driver's whole run passes its comparison on the CPU
at a small size, and each cell's control fails it."""

import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness
from bench import reference as ref
from bench.cells import serve, train, train_lsnn
from bench.model import make_weights
from bench.tests.helpers import CPU_PEAKS
from bench.trace import reduce_events

DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW_CELLS = ("lsnn_cue.train", "braille_q.serve")


def _run(workload, seed, config=None, **traffic):
    cell, cfg, tr = harness.resolve(harness.load_spec(), workload)
    return harness.Run(cell, dict(cfg, **(config or {})), dict(tr, **traffic),
                       seed, 1.0, 0, jax.devices()[:1], CPU_PEAKS,
                       time.perf_counter())


# The CPU learner runs the scan backend at float32 (HIGHEST): its reference
# reads float32 operands, not the TPU kernel's bfloat16.
CPU_LSNN = dict(contraction_operands="float32")
SMALL_LSNN = dict(samples_per_batch=8, dataset_samples=24)


def test_new_cells_resolve_by_name():
    spec = harness.load_spec()
    for name in NEW_CELLS:
        cell, config, tr = harness.resolve(spec, name)
        assert callable(harness.driver(tr["driver"]).run)
        e2e = {m["name"] for m in harness.metrics_for(spec, name, False)}
        per_layer = {m["name"] for m in harness.metrics_for(spec, name, True)}
        assert "setup_s" in e2e and len(e2e) == 2
        assert per_layer and all(callable(harness.reader(m)) for m in per_layer)


def test_lsnn_configuration_builds_the_programs_preset():
    from repro.configs import lsnn_evidence

    _, config, tr = harness.resolve(harness.load_spec(), "lsnn_cue.train")
    assert train_lsnn.rsnn_config(config) == lsnn_evidence.CONFIG
    assert train_lsnn.optimizer_config(config) == lsnn_evidence.OPT
    cue = lsnn_evidence.TASK
    assert {k: getattr(cue, k) for k in tr["cue"]} == tr["cue"]


def test_lsnn_readers_on_a_synthetic_trace():
    events = [
        (HOST, "t", "bench.window", 0, 1_000_000_000),
        (DEV, "XLA Ops", "%rsnn_train_alif.1 = f32[40,100] custom-call(...)",
         0, 300_000_000),
        (DEV, "XLA Ops", "%fusion = f32[8] fusion(...)", 400_000_000, 100_000_000),
    ]
    run = SimpleNamespace(
        trace=1, summary=reduce_events(events), config=_run(
            "lsnn_cue.train", 1).config, peaks=CPU_PEAKS,
        stats={"commits": 10, "samples": 640, "sample_ticks": 2250})
    assert harness.reader("kernel_ms_per_commit.lsnn")(run) == pytest.approx(30.0)
    assert harness.reader("device_idle_share.lsnn")(run) == pytest.approx(60.0)
    ops = 2.0 * (2 * (40 * 100 + 100 * 100 + 100 * 2) + 100 * 2) * 640 * 2250
    roof = harness.reader("train_kernels_roofline.lsnn")(run)
    assert roof == pytest.approx(100.0 * ops / 197e12 / 0.3)
    lif = SimpleNamespace(**dict(vars(run), summary=reduce_events(
        [(HOST, "t", "bench.window", 0, 1000),
         (DEV, "XLA Ops", "%rsnn_train.1 = f32[8] custom-call(...)", 0, 10)])))
    assert harness.reader("kernel_ms_per_commit.lsnn")(lif) is None
    assert harness.reader("train_kernels_roofline.lsnn")(lif) is None
    serve_run = SimpleNamespace(trace=1, summary=run.summary,
                                stats={"mean_batch": 3.5})
    assert harness.reader("batch_mean.serve")(serve_run) == 3.5
    assert harness.reader("device_idle_share.serve")(serve_run) == pytest.approx(60.0)


def test_lsnn_train_cell_on_the_cpu_is_correct():
    run = _run("lsnn_cue.train", 2**31 + 5, CPU_LSNN, **SMALL_LSNN)
    run.seconds = 0.5
    train_lsnn.run(run)
    run.check("compiles_in_window", run.stats["compiles_in_window"], 0)
    assert run.correct, run.checks
    assert run.stats["train_tiles"] == {"scan": run.stats["tiles"]}
    assert run.stats["tiles"] > 3


@pytest.mark.parametrize("by, off", [
    ({"rsnn_train_alif": 5}, 0),
    ({"rsnn_train_alif": 4}, 1),
    ({"rsnn_train": 5}, 10),
    ({"rsnn_train_alif": 5, "scan": 2}, 2),
    ({}, 5),
])
def test_lsnn_tile_counter_check(by, off):
    """The cell's check counts tiles off the adaptive kernel and tiles the
    counter missed."""
    stats = {"tiles": 5, "train_tiles": by}
    assert train_lsnn.tiles_off(stats, "rsnn_train_alif") == off


def test_lsnn_bf16_control_fails_a_limit():
    run = _run("lsnn_cue.train", 77, CPU_LSNN, **SMALL_LSNN)
    data = train.dataset(np.random.default_rng(1), run.config, run.traffic)
    w = train_lsnn._host(train_lsnn.make_weights(run.config, 2))
    states, first = train_lsnn.reference_first(run, data, w, control=True)
    train_lsnn.compare(run, data, w, states, first)
    assert not run.correct, run.checks


def test_serve_cell_on_the_cpu_is_correct_and_bf16_is_not():
    run = _run("braille_q.serve", 2**31 + 9, offered_events_per_s=3000,
               character_pool=24)
    run.seconds = 1.0
    serve.run(run)
    run.check("compiles_in_window", run.stats["compiles_in_window"], 0)
    assert run.correct, run.checks
    assert run.stats["requests"] > 5 and run.stats["mean_batch"] >= 1.0
    e = run.evidence
    dp = ref.Datapath.from_config(run.config)
    q = e["order"]
    stand_in = ref.run_streams(dp, {k: np.asarray(v) for k, v in e["weights"].items()},
                               e["requests"].raster(q), e["requests"].ticks[q],
                               rnd=ref.bf16)["acc_y"]
    control = _run("braille_q.serve", 1)
    serve.compare(control, dp, e["weights"], e["requests"], q, stand_in,
                  np.ones(len(q), bool))
    assert not control.correct, control.checks


def test_serve_schedule_offers_the_rate():
    run = _run("braille_q.serve", 3, offered_events_per_s=20000)
    req = serve.Requests(np.random.default_rng(1), run.traffic,
                         run.config["sample_ticks"], 10.0)
    due, idx = serve.schedule(5, req, run.traffic, 10.0)
    assert np.all(np.diff(due) >= 0) and len(set(idx.tolist())) == len(idx)
    offered = req.events[idx].sum() / 10.0
    assert abs(offered / 20000 - 1) < 0.05
    assert make_weights(run.config, 1)["w_in"].shape == (12, 38)


def test_serve_buffers_equal_the_plain_encoding():
    """The sliced buffers and event counts of the scheduled requests are
    those that encoding each request's raster on its own gives."""
    run = _run("braille_q.serve", 2**31 + 11, offered_events_per_s=20000,
               character_pool=24)
    req = serve.Requests(np.random.default_rng(4), run.traffic,
                         run.config["sample_ticks"], 2.0)
    due, idx = serve.schedule(6, req, run.traffic, 2.0)
    assert sorted(idx.tolist()) == list(range(len(due)))
    req.encode_first(len(due))
    assert len(req.bufs) == len(due)
    for q in range(len(due)):
        plain = req.encode(int(req.char[q]), int(req.ticks[q]))
        assert req.bufs[q].dtype == plain.dtype
        np.testing.assert_array_equal(req.bufs[q], plain)
        assert req.events[q] == len(plain) - 2
