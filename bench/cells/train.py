"""END_B online learning: ``OnlineLearner.train_batch`` fed by the ARM-mode
data pipeline (``make_pipeline("arm", ...)``), one commit per batch of
``samples_per_batch`` samples.

Set-up draws the dataset from the seed (the copied Braille or cue
generator), builds one learner with the benchmark's own weights and key, and
drives it through its first three commits with the window's own call and
feed, on batches whose rows all differ.  The window then goes on with that
same learner and feed, epoch after epoch, and closes when the last commit's
weights are ready on the device.

The reference learner (``bench/reference.py``) follows the first commit
from the same weights, batches and key, its e-prop contractions reading
their operands in the precision the configuration states
(``dw_operands``).  Compared (limits in ``bench/limits.json``, set from chip
readings as ``PERF.md`` records):

* ``first_spike_rate_gap``: the first commit's spike rate, relative gap;
* ``first_correct_diff``: the first commit's count of correct predictions;
* ``first_update_norm_gap``: the first update as the optimizer applied it
  (``W + acc`` after commit 1 minus before), gap of norms by the worst of
  the three weight matrices: ``|norm(program) - norm(reference)|`` over the
  larger of the reference's norm of that matrix and the median matrix's;
* ``first_code_steps_apart``: the 8-bit SRAM codes committed by the first
  commit, summed ``|code(program) - code(reference)|`` over every weight
  (the stochastic rounding and its key).

The second and third commits are not compared: one weight that rounds one
step apart in an early commit changes the next forward, and the roundings
after it then part by tens of steps, as far as the control's (``PERF.md``).
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from bench import events as ev
from bench.harness import Run, subseed

LIMITS = json.loads((Path(__file__).resolve().parents[1] / "limits.json")
                    .read_text())
FIRST_STEPS = 3


def _samples(rng, config: dict, tr: dict, n: int):
    """``n`` seeded samples as AER buffers, and their length in ticks."""
    if tr["generator"] == "braille":
        T = config["sample_ticks"]
        rasters, labels = ev.braille_characters(
            rng, tr["letters"], n, ev.BrailleParams(num_ticks=T))
        return [ev.encode_sample(r, int(l), int(T * tr["label_tick_frac"]),
                                 T - 1) for r, l in zip(rasters, labels)], T
    p = ev.CueParams(**tr["cue"])
    return [ev.encode_sample(*ev.cue_sample(rng, p)) for _ in range(n)], \
        p.num_ticks


def dataset(rng, config: dict, tr: dict) -> dict:
    """The seeded training split as AER buffers padded to the traffic's
    ``event_words``, so every seed gives the programs the same shapes (and
    set-up finds them in the compile cache).  A sample with more words, far
    out in the tail, is drawn again."""
    n, width = tr["dataset_samples"], tr["event_words"]
    bufs = []
    while len(bufs) < n:
        got, T = _samples(rng, config, tr, n - len(bufs))
        bufs += [b for b in got if len(b) <= width]
    if T != config["sample_ticks"]:
        raise ValueError(f"traffic makes {T}-tick samples, the configuration "
                         f"states {config['sample_ticks']}")
    return {"train": {"events": ev.pad_events(bufs, width),
                      "n_in": config["n_in"], "num_ticks": T}}


def _host(tree):
    return {k: np.asarray(v, np.float64) for k, v in tree.items()}


def _norm_gap(prog: dict, ref: dict) -> float:
    norms = {k: np.linalg.norm(v) for k, v in ref.items()}
    floor = float(np.median(list(norms.values())))
    return max(abs(np.linalg.norm(prog[k]) - norms[k]) / max(norms[k], floor)
               for k in ref)


def run(run: Run) -> None:
    import jax
    from repro.core.controller import ControllerConfig, OnlineLearner
    from repro.data.pipeline import make_pipeline

    from bench.model import make_weights, optimizer_config, rsnn_config

    c, tr = run.config, run.traffic
    spb = tr["samples_per_batch"]
    run.mark("importing the program")
    data = dataset(np.random.default_rng(subseed(run.seed, 1)), c, tr)
    run.mark("drawing the dataset")
    weights = make_weights(c, subseed(run.seed, 2))
    learner = OnlineLearner(
        rsnn_config(c, c["sample_ticks"]),
        ControllerConfig(commit="batch", samples_per_batch=spb),
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
    states = [(_host(learner.weights), _host(learner.opt_state["acc"]))]
    seen = []
    for _ in range(FIRST_STEPS):
        m = learner.train_batch(next(it))
        seen.append({k: float(m[k]) for k in ("correct", "spike_rate")})
        states.append((_host(learner.weights), _host(learner.opt_state["acc"])))
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
                     commit_host_s=host_s, sample_ticks=c["sample_ticks"])
    run.read_memory()
    del learner, pipe, it
    run.evidence = {"data": data, "weights": weights, "states": states,
                    "seen": seen}
    compare(run, data, weights, states, seen)


def operands(config: dict):
    """The rounding of the e-prop contractions' operands the configuration
    states (``bench/reference.py``'s ``OPERANDS``)."""
    from bench.reference import OPERANDS

    return OPERANDS[config["dw_operands"]]


def reference_steps(run: Run, data, weights, n: int, **kw):
    """The reference learner's first ``n`` commits: per-commit metrics and
    the ``(W, acc)`` states before and after each.  ``kw`` goes to
    :class:`bench.reference.Learner` (a stand-in's precision or fault)."""
    import jax

    from bench.reference import Datapath, Learner

    c, spb = run.config, run.traffic["samples_per_batch"]
    o = c["optimizer"]
    kw.setdefault("ops", operands(c))
    ref = Learner(Datapath.from_config(c), _host(weights),
                  jax.random.key(subseed(run.seed, 4)), o["lr"], o["clip"],
                  **kw)
    raster, labels, valid = ev.decode(data["train"]["events"], c["n_in"],
                                      data["train"]["num_ticks"])
    states = [(dict(ref.w), dict(ref.acc))]
    seen = []
    for i in range(n):
        b = slice(i * spb, (i + 1) * spb)
        seen.append(ref.train_batch(raster[:, b], valid[:, b], labels[b]))
        states.append((dict(ref.w), dict(ref.acc)))
    return states, seen


def readings(config: dict, prog_states, prog_seen, ref_states,
             ref_seen) -> dict:
    """The numbers a run compares, of a program (or a stand-in) against the
    reference."""
    from bench.reference import Datapath

    codes = Datapath.from_config(config).codes
    (w0, a0), (w1, a1) = prog_states[0], prog_states[1]
    (v0, b0), (v1, b1) = ref_states[0], ref_states[1]
    p, r = prog_seen[0], ref_seen[0]
    return {
        "first_update_norm_gap": _norm_gap(
            {k: (w1[k] + a1[k]) - (w0[k] + a0[k]) for k in w0},
            {k: (v1[k] + b1[k]) - (v0[k] + b0[k]) for k in v0}),
        "first_spike_rate_gap": (abs(p["spike_rate"] - r["spike_rate"])
                                 / max(r["spike_rate"], 1e-12)),
        "first_correct_diff": abs(p["correct"] - r["correct"]),
        "first_code_steps_apart": int(sum(
            np.abs(codes(w1[k]) - codes(v1[k])).sum() for k in w1)),
    }


def compare(run: Run, data, weights, states, seen) -> None:
    """Check the first commit of the program (or of a stand-in put in its
    place) against the reference's, each number against its limit."""
    ref_states, ref_seen = reference_steps(run, data, weights, 1)
    got = readings(run.config, states, seen, ref_states, ref_seen)
    for name, limit in LIMITS[run.cell["name"]].items():
        run.check(name, got[name], limit)
