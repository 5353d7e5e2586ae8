"""A run whose timed path is broken underneath comes out not correct: the
harness's whole run on the CPU at a small size, without the look for a chip.

Faults each cell can have: a step that returns its state unchanged, half of
the batch left out (the mean taken over the rest), and an answer altered
where it is produced; for training cells also the commit rounding to
nearest instead of stochastically.  No cell spans chips, so the exchange between chips
has no fault of its own.
"""

import jax.numpy as jnp
import pytest

from bench.tests.helpers import SMALL_SESSIONS, cpu_run

SMALL_TRAIN = {"braille_q.train": dict(dataset_samples=280),
               "cue_q.train": dict(dataset_samples=60)}


def _sessions_fault(kind):
    from repro.core.backend import ExecutionBackend

    orig = ExecutionBackend.step_sessions

    def broken(self, weights, raster, live, valid, state):
        out = orig(self, weights, raster, live, valid, state)
        if kind == "unchanged":
            return dict(state)
        if kind == "half":
            half = raster.shape[1] // 2
            return {k: v.at[half:].set(state[k][half:]) for k, v in out.items()}
        return dict(out, acc_y=out["acc_y"].at[0, 0].add(1.0))

    return broken


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("workload", ["braille_q.sessions",
                                      "braille_q.sessions_rate"])
def test_broken_session_step_is_not_correct(monkeypatch, workload, kind):
    from repro.core.backend import ExecutionBackend

    monkeypatch.setattr(ExecutionBackend, "step_sessions", _sessions_fault(kind))
    extra = dict(offered_events_per_s=5000) if "rate" in workload else {}
    run = cpu_run(workload, seed=21, seconds=0.5, **SMALL_SESSIONS, **extra)
    assert not run.correct, run.checks


def _train_fault(kind):
    from repro.core.backend import ExecutionBackend
    from repro.optim.eprop_opt import EpropSGD

    if kind == "unchanged":
        return EpropSGD, "update", lambda self, w, dw, state, key=None, \
            num_updates=1.0: (w, state)
    if kind == "nearest":
        from repro.core.quant import QuantSpec

        return QuantSpec, "round_stochastic", \
            lambda self, x, key: self.round_nearest(x)
    orig = ExecutionBackend.train_tile

    def broken(self, weights, raster, y_star, valid):
        if kind == "half":
            h = raster.shape[1] // 2
            dw, _ = orig(self, weights, raster[:, :h], y_star[:h], valid[:, :h])
            _, m = orig(self, weights, raster, y_star, valid)
            return {k: 2.0 * v for k, v in dw.items()}, m
        dw, m = orig(self, weights, raster, y_star, valid)
        return dict(dw, w_in=dw["w_in"] * jnp.float32(1.01)), m

    return ExecutionBackend, "train_tile", broken


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered", "nearest"])
@pytest.mark.parametrize("workload", sorted(SMALL_TRAIN))
def test_broken_training_step_is_not_correct(monkeypatch, workload, kind):
    cls, name, fn = _train_fault(kind)
    monkeypatch.setattr(cls, name, fn)
    run = cpu_run(workload, seed=22, seconds=0.5, **SMALL_TRAIN[workload])
    assert not run.correct, run.checks


@pytest.mark.parametrize("workload", ["braille_q.sessions"] + sorted(SMALL_TRAIN))
def test_sound_run_is_correct(workload):
    kw = SMALL_SESSIONS if "sessions" in workload else SMALL_TRAIN[workload]
    run = cpu_run(workload, seed=23, seconds=0.5, **kw)
    assert run.correct, run.checks
