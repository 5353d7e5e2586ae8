"""END_B online learning of the LSNN (``lsnn_cue``): adaptive-threshold
neurons, float datapath, samples of 2,250 ticks.

The same walk as ``bench/cells/train.py``, whose dataset and feed it
imports: set-up draws the cue split from the seed, builds one
``OnlineLearner`` with the benchmark's own weights and key, and drives it
through three commits with the window's own call and feed (``make_pipeline
("arm")`` -> jitted decode -> ``OnlineLearner.train_batch`` -> the adaptive
fused kernel ``rsnn_train_alif``).  The window goes on with that learner,
epoch after epoch, and closes when the last commit's weights are ready.

The first commit is compared with ``bench/reference_lsnn.py`` from the same
weights and batch, its contractions reading their operands in the precision
the configuration states (``contraction_operands``).  Compared (limits in
``bench/limits_lsnn.json``, set from chip readings as ``PERF.md`` records):

* ``first_spike_rate_gap.lif`` / ``.alif``: each population's spike rate,
  relative gap;
* ``first_acc_y_gap``: the readout accumulators (logits, not their argmax),
  ``|acc(program) - acc(reference)| / |acc(reference)|`` over the batch;
* ``first_update_norm_gap.<matrix>``: the first update as the optimizer
  applied it (``W`` after commit 1 minus before), gap of norms per weight
  matrix over the reference's norm;
* ``tiles_off_alif_kernel``: train tiles the learner ran through any branch
  but the adaptive fused kernel's (on a TPU; the scan's where Pallas only
  interprets), or that its counter missed.  The backend counts each
  dispatched tile under the branch its ``_train_impl`` took when traced
  (``train_tiles``); this must be 0.
"""

from __future__ import annotations

import functools
import json
import math
import time
from pathlib import Path

import numpy as np

from bench import events as ev
from bench.cells.train import FIRST_STEPS, dataset
from bench.harness import Run, subseed

LIMITS = json.loads((Path(__file__).resolve().parents[1] / "limits_lsnn.json")
                    .read_text())
MATRICES = ("w_in", "w_rec", "w_out")


def rsnn_config(c: dict):
    """The configuration file as the program's ``RSNNConfig``."""
    from repro.core.eprop import EpropConfig
    from repro.core.neuron import NeuronConfig
    from repro.core.rsnn import RSNNConfig

    return RSNNConfig(
        n_in=c["n_in"], n_hid=c["n_hid"], n_out=c["n_out"],
        num_ticks=c["sample_ticks"],
        neuron=NeuronConfig(
            alpha=math.exp(-1.0 / c["tau_m_ticks"]),
            kappa=math.exp(-1.0 / c["tau_out_ticks"]),
            v_th=c["v_th"], reset=c["reset"], surrogate=c["surrogate"],
            gamma=c["gamma"], n_adaptive=c["n_adaptive"], beta=c["beta"],
            tau_a=float(c["tau_a_ticks"])),
        eprop=EpropConfig(mode="factored", error=c["error"],
                          infer_window=c["infer_window"]),
        w_in_gain=c["w_in_gain"], label_delay=c["label_delay"])


def optimizer_config(c: dict):
    from repro.optim.eprop_opt import EpropSGDConfig

    return EpropSGDConfig(lr=c["optimizer"]["lr"], clip=c["optimizer"]["clip"])


@functools.lru_cache(maxsize=None)
def _init_fn(n_in, n_hid, n_out, w_in_gain):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def init(key):
        k_in, k_rec, k_out = jax.random.split(key, 3)

        def draw(k, shape, gain):
            return gain * jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
                jnp.float32(shape[0]))

        return {"w_in": draw(k_in, (n_in, n_hid), w_in_gain),
                "w_rec": draw(k_rec, (n_hid, n_hid), 1.0),
                "w_out": draw(k_out, (n_hid, n_out), 1.0)}

    return init


def make_weights(c: dict, seed: int):
    """Seeded Gaussian weights with fan-in scaling, float32, drawn on the
    device (the benchmark's own, off any SRAM grid)."""
    import jax

    init = _init_fn(c["n_in"], c["n_hid"], c["n_out"], float(c["w_in_gain"]))
    return init(jax.random.key(seed))


def _host(tree):
    return {k: np.asarray(tree[k], np.float64) for k in MATRICES}


def run(run: Run) -> None:
    import jax
    from repro.core.controller import ControllerConfig, OnlineLearner
    from repro.data.pipeline import make_pipeline

    c, tr = run.config, run.traffic
    spb = tr["samples_per_batch"]
    run.mark("importing the program")
    data = dataset(np.random.default_rng(subseed(run.seed, 1)), c, tr)
    run.mark("drawing the dataset")
    weights = make_weights(c, subseed(run.seed, 2))
    learner = OnlineLearner(
        rsnn_config(c), ControllerConfig(commit="batch", samples_per_batch=spb),
        optimizer_config(c), jax.random.key(subseed(run.seed, 3)))
    learner.weights = dict(weights)
    learner.opt_state = learner.opt.init(learner.weights)
    learner.key = jax.random.key(subseed(run.seed, 4))
    pipe = make_pipeline("arm", data, samples_per_batch=spb)
    jax.block_until_ready(learner.weights)
    run.mark("weights and learner")

    def feed():
        epoch = 0
        while True:
            yield from pipe.batches("train", epoch)
            epoch += 1

    it = feed()
    states = [_host(learner.weights)]
    first = None
    for _ in range(FIRST_STEPS):
        m = learner.train_batch(next(it))
        if first is None:
            first = {k: np.asarray(m[k], np.float64)
                     for k in ("acc_y", "spike_rate_pop")}
        states.append(_host(learner.weights))
    run.mark("first three commits (compiles)")

    commits = 0
    host_s = 0.0
    with run.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            a = time.perf_counter()
            with run.span("commit"):
                learner.train_batch(next(it))
            host_s += time.perf_counter() - a
            commits += 1
        with run.span("drain"):
            jax.block_until_ready(learner.weights)
    run.attempted = commits * spb
    run.e2e["train_samples_per_s"] = commits * spb / run.window_s
    run.stats.update(commits=commits, samples=commits * spb,
                     commit_host_s=host_s, sample_ticks=c["sample_ticks"],
                     train_tiles=dict(learner.backend.train_tiles),
                     tiles=commits + FIRST_STEPS)
    run.read_memory()
    del learner, pipe, it
    run.evidence = {"data": data, "weights": weights, "states": states,
                    "first": first}
    compare(run, data, _host(weights), states, first)


def reference_first(run: Run, data, w, **kw):
    """The reference's first commit: ``(W before, W after)`` and its
    metrics.  ``kw`` goes to :func:`bench.reference_lsnn.eprop_dw` (the
    stand-in's precision)."""
    from bench import reference_lsnn as ref

    c, spb = run.config, run.traffic["samples_per_batch"]
    kw.setdefault("ops", c["contraction_operands"])
    raster, labels, valid = ev.decode(data["train"]["events"], c["n_in"],
                                      data["train"]["num_ticks"])
    b = slice(0, spb)
    dw, m = ref.eprop_dw(c, w, raster[:, b], valid[:, b], labels[b], **kw)
    return [dict(w), ref.commit(c, w, dw, spb)], m


def readings(prog_states, prog_first, ref_states, ref_first) -> dict:
    """The numbers a run compares, of a program (or a stand-in) against the
    reference."""
    out = {}
    for i, pop in enumerate(("lif", "alif")):
        p, r = prog_first["spike_rate_pop"][i], ref_first["spike_rate_pop"][i]
        out[f"first_spike_rate_gap.{pop}"] = abs(p - r) / max(r, 1e-12)
    a_p, a_r = prog_first["acc_y"], ref_first["acc_y"]
    out["first_acc_y_gap"] = float(np.linalg.norm(a_p - a_r)
                                   / max(np.linalg.norm(a_r), 1e-12))
    for k in MATRICES:
        u_p = prog_states[1][k] - prog_states[0][k]
        u_r = ref_states[1][k] - ref_states[0][k]
        n_r = np.linalg.norm(u_r)
        out[f"first_update_norm_gap.{k}"] = float(
            abs(np.linalg.norm(u_p) - n_r) / max(n_r, 1e-12))
    return out


def compare(run: Run, data, w, states, first) -> None:
    """Check the first commit of the program (or of a stand-in put in its
    place) against the reference's, each number against its limit, and the
    program counter of train tiles by kernel."""
    ref_states, ref_m = reference_first(run, data, w)
    got = readings(states, first, ref_states, ref_m)
    for name, limit in LIMITS[run.cell["name"]].items():
        run.check(name, got[name], limit)
    st = run.stats
    if "tiles" in st:
        want = ("rsnn_train_alif" if run.devices[0].platform == "tpu"
                else "scan")
        run.check("tiles_off_alif_kernel", tiles_off(st, want), 0)


def tiles_off(stats: dict, want: str) -> int:
    """Train tiles the backend counted under a branch other than ``want``,
    plus those of the ``stats["tiles"]`` dispatched that it did not count."""
    by = stats["train_tiles"]
    return (abs(stats["tiles"] - by.get(want, 0))
            + sum(n for k, n in by.items() if k != want))
