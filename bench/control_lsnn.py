"""Readings that set the limits of ``lsnn_cue.train``'s ``correct``: run on
the chip, not by the benchmark's own runs.

    python3 bench/control_lsnn.py --seeds 1,2,3 --control-seeds 4,5,6 --seconds 2

In one process, for each of ``--seeds``, a whole run of the cell (a short
window at the cell's own sizes) gives the program's readings, the lower end
of each limit, against the reference at both contraction precisions
(``ops``: float32 and bfloat16 operands): the base the program sits nearest
is the precision the configuration states.  For each of
``--control-seeds``, the reference one precision lower — every carried
float and every contraction's result rounded to bfloat16 — is put in the
program's place and goes through the cell's own comparison, which has to
come out not correct: the upper end.  One JSON line per run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

WORKLOAD = "lsnn_cue.train"


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    opts = ap.parse_args(argv)
    spec = harness.load_spec()
    cell, config, tr = harness.resolve(spec, WORKLOAD)
    sys.path.insert(0, str(harness.ROOT / "src"))
    devices = harness.find_chips(int(cell["chips"]))
    peaks = harness.peaks_for(devices[0].device_kind)
    harness.enable_cache()
    import numpy as np

    from bench.cells import train, train_lsnn

    def new_run(seed):
        return harness.Run(cell, config, tr, seed, opts.seconds, 0, devices,
                           peaks, time.perf_counter())

    def checks(run):
        return {n: [v, lim] for n, v, lim in run.checks}

    def bases(run, data, w, states, first):
        out = {}
        for ops in ("float32", "bfloat16"):
            r_states, r_first = train_lsnn.reference_first(run, data, w, ops=ops)
            out[ops] = train_lsnn.readings(states, first, r_states, r_first)
        return out

    for seed in _seeds(opts.seeds):
        run = new_run(seed)
        train_lsnn.run(run)
        run.check("compiles_in_window", run.stats["compiles_in_window"], 0)
        e = run.evidence
        print(json.dumps({
            "who": "program", "seed": seed, "correct": run.correct,
            "checks": checks(run), "e2e": dict(run.e2e),
            "bases": bases(run, e["data"], train_lsnn._host(e["weights"]),
                           e["states"], e["first"])}), flush=True)

    for seed in _seeds(opts.control_seeds):
        run = new_run(seed)
        data = train.dataset(np.random.default_rng(harness.subseed(seed, 1)),
                             config, tr)
        w = train_lsnn._host(train_lsnn.make_weights(
            config, harness.subseed(seed, 2)))
        states, first = train_lsnn.reference_first(run, data, w, control=True)
        train_lsnn.compare(run, data, w, states, first)
        print(json.dumps({"who": "control_bf16", "seed": seed,
                          "correct": run.correct, "checks": checks(run),
                          "bases": bases(run, data, w, states, first)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
