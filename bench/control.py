"""Readings that set the limits of ``correct``: run on the chip, not by the
benchmark's own runs.

    python3 bench/control.py --workload braille_q.train --seeds 1,2,3 \\
        --control-seeds 4,5,6 --seconds 2

In one process, for each of ``--seeds``, a whole run of the cell (a short
window at the cell's own sizes and load) gives the program's readings: the
lower end of each limit.  For each of ``--control-seeds``, stand-ins are put
in the program's place and go through the cell's own comparison, which has
to come out not correct: the reference in bfloat16 (the control) and, for
training cells, the reference with half of the batch left out or rounding
to nearest instead of stochastically.  Their readings give the upper end.
One JSON line per run on standard output.

For training cells each line also gives every reading against the
reference at both contraction precisions (``bench/reference.py``'s
``ops``): float64 operands and bfloat16 operands, each accumulated in
float64; the relative norm of the first update's difference, by the
worst matrix (the witness for the precision the configuration states); and
the uncompared gap of norms of the weights' change over three commits.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def _update_diff(a, b) -> float:
    """Worst matrix's ``|u_a - u_b| / |u_b|`` of the first update."""
    import numpy as np

    (w0, a0), (w1, a1) = a[0], a[1]
    (v0, b0), (v1, b1) = b[0], b[1]
    out = 0.0
    for k in w0:
        ua = (w1[k] + a1[k]) - (w0[k] + a0[k])
        ub = (v1[k] + b1[k]) - (v0[k] + b0[k])
        out = max(out, float(np.linalg.norm(ua - ub) / np.linalg.norm(ub)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    opts = ap.parse_args(argv)
    spec = harness.load_spec()
    cell, config, tr = harness.resolve(spec, opts.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    devices = harness.find_chips(int(cell["chips"]))
    peaks = harness.peaks_for(devices[0].device_kind)
    harness.enable_cache()
    import numpy as np

    from bench import reference as ref
    from bench.cells import sessions, train
    from bench.model import make_weights

    def new_run(seed):
        return harness.Run(cell, config, tr, seed, opts.seconds, 0, devices,
                           peaks, time.perf_counter())

    def run_cell(seed):
        run = new_run(seed)
        harness.driver(tr["driver"]).run(run)
        run.check("compiles_in_window", run.stats["compiles_in_window"], 0)
        return run

    def checks(run):
        return {n: [v, lim] for n, v, lim in run.checks}

    bases = {"f64": dict(ops=None), "bf16_ops": dict(ops=ref.bf16)}

    def against_bases(run, data, w, states, seen):
        """Every reading of ``states`` against each reference base."""
        out = {}
        for name, kw in bases.items():
            r = train.reference_steps(run, data, w, train.FIRST_STEPS, **kw)
            got = train.readings(config, states, seen, *r)
            got["first_update_diff"] = _update_diff(states, r[0])
            got["change_norm_gap"] = train._norm_gap(
                *({k: st[-1][0][k] - st[0][0][k] for k in st[0][0]}
                  for st in (states, r[0])))
            out[name] = got
        return out

    for seed in _seeds(opts.seeds):
        run = run_cell(seed)
        line = {"who": "program", "seed": seed, "correct": run.correct,
                "checks": checks(run),
                "e2e": {k: float(v) for k, v in run.e2e.items()}}
        if tr["driver"] == "train":
            e = run.evidence
            line["bases"] = against_bases(run, e["data"], e["weights"],
                                          e["states"], e["seen"])
        print(json.dumps(line), flush=True)

    for seed in _seeds(opts.control_seeds):
        if tr["driver"] == "train":
            data = train.dataset(np.random.default_rng(harness.subseed(seed, 1)),
                                 config, tr)
            w = make_weights(config, harness.subseed(seed, 2))
            spb = tr["samples_per_batch"]
            ops = train.operands(config)
            for who, kw in (("control_bf16", dict(rnd=ref.bf16)),
                            ("fault_half_batch", dict(rows=slice(0, spb // 2))),
                            ("fault_round_nearest", dict(nearest=True))):
                run = new_run(seed)
                stand_in = train.reference_steps(
                    run, data, w, train.FIRST_STEPS, ops=ops, **kw)
                train.compare(run, data, w, *stand_in)
                print(json.dumps({
                    "who": who, "seed": seed, "correct": run.correct,
                    "checks": checks(run),
                    "bases": against_bases(run, data, w, *stand_in)}),
                    flush=True)
        else:
            run = run_cell(seed)
            program = checks(run)
            e = run.evidence
            dp = ref.Datapath.from_config(config)
            stand_in = new_run(seed)
            stand_in.stats["quarantined"] = 0
            sessions.compare(stand_in, dp, e["weights"], e["streams"],
                             e["ticks"], sessions.stand_in(
                                 dp, e["weights"], e["streams"], e["ticks"],
                                 rnd=ref.bf16))
            print(json.dumps({"who": "control_bf16", "seed": seed,
                              "correct": stand_in.correct,
                              "checks": checks(stand_in),
                              "program": program}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
