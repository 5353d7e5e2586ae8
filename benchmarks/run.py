"""Benchmark entry point: one benchmark per paper table/figure.

  Fig. 6  cue accumulation (both controller modes)  -> bench_cue
  Fig. 7/8 Braille online learning (both commits)   -> bench_braille
  T1/T2   resource analog (two SoC modes)           -> bench_resources
  kernels allclose + µbench                         -> bench_kernels
  serving batched vs sequential throughput          -> bench_serve
  stateful session streaming (events/s, tick p99)   -> bench_serve --streaming
  multi-model serving (Braille + cue, one engine)   -> bench_serve --multi-model
  achieved-vs-roofline bandwidth + Bt auto-tune     -> roofline

``--fast`` also swaps the full cue run for its 3-seed END_B-vs-END_S
acceptance smoke (``bench_cue --smoke``); its section folds into
``BENCH_train.json`` under ``"cue"``, and the multi-model per-model
throughput folds into ``BENCH_serve.json`` under ``"multi_model"``.

``python -m benchmarks.run [--fast]`` — default runs the paper's full
200-epoch Braille protocol; ``--fast`` trims braille to its 12-epoch smoke
(throughput + commit-mode parity) and shrinks the serving stream.

Machine-readable outputs (the cross-PR perf trajectory, uploaded as CI
artifacts): ``BENCH_train.json`` (training samples/sec per commit mode +
accuracy), ``BENCH_serve.json`` (serving samples/sec, p50/p99 latency) and
``BENCH_kernels.json`` (per-op samples/s + analytic HBM bytes-streamed,
written by ``bench_kernels`` itself — its traffic-ratio gates are what the
kernels smoke lane enforces) are written to ``--out-dir`` (default: cwd)
for every run that includes the corresponding benchmark.

Benchmarks return either data rows, or a dict with an ``"rc"`` exit code
plus payloads run.py folds into the JSON reports; a non-zero rc (or an
exception) fails the whole run — CI propagates it.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback
from pathlib import Path


def _write_report(path: Path, payload: dict) -> None:
    import jax

    payload = {
        "schema": 1,
        "unix_time": time.time(),
        "jax_backend": jax.default_backend(),
        "host": platform.machine(),
        **payload,
    }
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--out-dir", default=".")
    opts = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import bench_cue, bench_kernels, bench_resources
    from benchmarks import bench_braille, bench_serve, roofline

    jobs = [
        ("kernels", lambda: bench_kernels.main(
            ["--out-dir", opts.out_dir] + (["--smoke"] if opts.fast else []))),
        ("serve", lambda: bench_serve.main(["--fast"] if opts.fast else [])),
        ("streaming", lambda: bench_serve.main(
            ["--streaming"] + (["--fast"] if opts.fast else []))),
        ("multi_model", lambda: bench_serve.main(
            ["--multi-model"] + (["--fast"] if opts.fast else []))),
        ("cue", lambda: bench_cue.main(["--smoke"] if opts.fast else [])),
        ("resources", lambda: bench_resources.main([])),
        ("braille", lambda: bench_braille.main(
            ["--smoke"] if opts.fast else ["--epochs", "200"])),
        ("roofline", lambda: roofline.main(["--bench-dir", opts.out_dir])),
    ]
    failures = []
    reports = {}
    for name, fn in jobs:
        if opts.only and name not in opts.only.split(","):
            continue
        print(f"\n===== {name} =====", flush=True)
        try:
            rc = fn()
            if isinstance(rc, dict):
                reports[name] = rc
                rc = rc.get("rc", 0)
            # benches return data rows for callers; an int is an exit code
            if isinstance(rc, int) and rc != 0:
                failures.append(name)
        except Exception:
            traceback.print_exc()
            failures.append(name)

    out_dir = Path(opts.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    if "braille" in reports or "cue" in reports:
        payload = {"benchmark": "braille_training"}
        if "braille" in reports:
            r = reports["braille"]
            payload["rows"] = r.get("rows", [])
            payload["throughput"] = r.get("throughput")
        if "cue" in reports and reports["cue"].get("cue"):
            payload["cue"] = reports["cue"]["cue"]
        _write_report(out_dir / "BENCH_train.json", payload)
    if any(
        k in reports and reports[k].get(v)
        for k, v in (("serve", "serve"), ("streaming", "streaming"),
                     ("multi_model", "multi_model"))
    ):
        payload = {"benchmark": "batched_serving"}
        if "serve" in reports:
            payload.update(reports["serve"].get("serve") or {})
        if "streaming" in reports:
            payload["streaming"] = reports["streaming"]["streaming"]
        if "multi_model" in reports:
            payload["multi_model"] = reports["multi_model"]["multi_model"]
        _write_report(out_dir / "BENCH_serve.json", payload)

    if failures:
        print(f"\nFAILED: {failures}")
        return 1
    print("\nall benchmarks complete")
    return 0


if __name__ == "__main__":
    sys.exit(main())
