"""Each cell's control, the reference put in the program's place in the
nearest lower precision (bfloat16 carriers), goes through the cell's own
comparison and comes out not correct."""

import time

import jax
import numpy as np
import pytest

from bench import harness
from bench import reference as ref
from bench.cells import sessions, train
from bench.cells.sessions import Streams
from bench.model import make_weights
from bench.tests.helpers import CPU_PEAKS


def _run(workload, seed, **traffic):
    cell, config, tr = harness.resolve(harness.load_spec(), workload)
    return harness.Run(cell, config, dict(tr, **traffic), seed, 1.0, 0,
                       jax.devices()[:1], CPU_PEAKS, time.perf_counter())


def test_bf16_session_state_fails_the_exact_comparison():
    run = _run("braille_q.sessions", 9)
    run.stats["quarantined"] = 0
    streams = Streams(np.random.default_rng(4), 64, 2, 16, run.traffic["letters"],
                      run.config["sample_ticks"], run.traffic["feed_ticks"])
    dp = ref.Datapath.from_config(run.config)
    w = make_weights(run.config, 9)
    ticks = np.random.default_rng(5).integers(100, 500, 64)
    sessions.compare(run, dp, w, streams, ticks,
                     sessions.stand_in(dp, w, streams, ticks, rnd=ref.bf16))
    assert not run.correct, run.checks


@pytest.mark.parametrize("workload", ["braille_q.train", "cue_q.train"])
def test_bf16_learner_fails_a_training_limit(workload):
    run = _run(workload, 31)
    run.traffic["dataset_samples"] = 3 * run.traffic["samples_per_batch"]
    data = train.dataset(np.random.default_rng(1), run.config, run.traffic)
    w = make_weights(run.config, 2)
    train.compare(run, data, w, *train.reference_steps(
        run, data, w, train.FIRST_STEPS, rnd=ref.bf16))
    assert not run.correct, run.checks
    jax.clear_caches()
