"""Find the knee of an open-loop sessions cell: the highest offered event
rate the engine sustains without its lateness growing.  Run on the chip,
once per benchmark PR that defines or moves such a cell, one process per
rate (each reseats its fleet from zero):

    for r in 100000 200000 300000; do
        python3 bench/knee.py --workload braille_q.sessions_rate --rate $r
    done

Each prints one JSON line: the offered and served rates, the result latency
p50/p95 over all feeds and p95 over the first and the second half of the
window (by due time), and the generator's lag.  Below the knee the two
halves agree; above it the second half's tail keeps growing, because the
loop that hands feeds to the engine falls further behind.  The cell's
traffic file then fixes ``offered_events_per_s`` at four fifths of the knee.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="braille_q.sessions_rate")
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    opts = ap.parse_args(argv)
    spec = harness.load_spec()
    cell, config, tr = harness.resolve(spec, opts.workload)
    tr = dict(tr, offered_events_per_s=opts.rate)
    sys.path.insert(0, str(harness.ROOT / "src"))
    devices = harness.find_chips(int(cell["chips"]))
    harness.enable_cache()
    import numpy as np

    run = harness.Run(cell, config, tr, opts.seed, opts.seconds, 0, devices,
                      harness.peaks_for(devices[0].device_kind),
                      time.perf_counter())
    harness.driver(tr["driver"]).run(run)
    lat, due = run.evidence["latency_s"], run.evidence["due_s"]
    first = due < opts.seconds / 2
    pct = lambda a, q: 1e3 * float(np.percentile(a, q)) if len(a) else None
    print(json.dumps({
        "rate": opts.rate, "offered": run.stats["offered_events_per_s"],
        "served": float(run.e2e["served_events_per_s"]),
        "p50_ms": pct(lat, 50), "p95_ms": pct(lat, 95),
        "p95_first_half_ms": pct(lat[first], 95),
        "p95_second_half_ms": pct(lat[~first], 95),
        "gen_lag_p95_ms": run.stats["gen_lag_p95_ms"],
        "feeds": int(len(lat)), "correct": run.correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
