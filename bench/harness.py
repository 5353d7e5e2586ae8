"""One run of one benchmark cell, as ``BENCHMARK.json`` names it.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data.  A workload names a configuration (its file
under ``bench/configs/``) and a traffic mix (``bench/traffic/<name>.json``);
the traffic file names the driver that replays it
(``bench/cells/<driver>.py``), and each per-layer metric is read by
``bench/metrics/<metric name>.py``.  A new cell or metric is new files and a
new ``BENCHMARK.json`` entry; nothing here changes.

A driver gets a :class:`Run`.  It builds the system and draws all of its
traffic from the seed, calls :meth:`Run.window` to time the replay (set-up
ends where the window starts), reads device memory with
:meth:`Run.read_memory`, then compares what the timed path produced with
the reference and records each compared number with :meth:`Run.check`.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"


class NoChip(RuntimeError):
    pass


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str):
    """``(workload entry, configuration dict, traffic dict)`` by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r} (have {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metrics_for(spec: dict, workload: str, trace: bool) -> List[dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_module(path: Path):
    """Import one file by path (metric readers have dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str) -> Callable:
    return load_module(BENCH / "metrics" / f"{name}.py").read


def driver(name: str):
    return load_module(BENCH / "cells" / f"{name}.py")


def subseed(seed: int, *tags: int) -> int:
    """A 31-bit seed derived from the run's seed (any size) and tags."""
    import numpy as np

    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), *tags])
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoChip(f"no peaks for device_kind {kind!r} in bench/peaks.json "
                     f"(known: {', '.join(table)})")
    return table[kind]


def find_chips(need: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < need:
        raise NoChip(f"needs {need} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s) "
                     f"({devices[0].device_kind})")
    return devices[:need]


class CompileCounter:
    """Counts programs built, through ``jax.monitoring``: every XLA compile
    request (``backend_compile`` runs on a persistent-cache hit too), and
    of those the cache's hits and misses."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.count = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **kw):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def _on_duration(self, event, duration, **kw):
        if event == self.BUILD:
            self.count += 1


class Run:
    """What a driver sees of one run."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, devices,
                 peaks, t_start):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.peaks, self.t_start = devices, peaks, t_start
        self.compiles = CompileCounter()
        self.stats: Dict[str, float] = {}
        self.e2e: Dict[str, float] = {}
        self.checks: List[tuple] = []
        self.attempted = 0
        self.failed = 0
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.memory_peak: Optional[int] = None
        self.summary = None      # trace.TraceSummary of a traced run
        self.setup_parts: Dict[str, float] = {}
        self._last_mark = t_start

    def mark(self, part: str) -> None:
        """Attribute set-up time since the previous mark to ``part``."""
        now = time.perf_counter()
        self.setup_parts[part] = now - self._last_mark
        self._last_mark = now

    # ----------------------------------------------------------- spans
    def span(self, name: str):
        """A host span ``bench.<name>`` in the profiler's trace (traced runs
        only; a no-op otherwise)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    @contextlib.contextmanager
    def window(self):
        """Set-up ends here; the body is the measured window.  Set-up's
        objects are frozen out of the collector's way; no compilation may
        happen inside."""
        import jax

        gc.collect()
        gc.freeze()
        self.mark("rest of set-up")
        self.setup_s = time.perf_counter() - self.t_start
        before = self.compiles.count
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        t0 = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            if self.trace:
                jax.profiler.stop_trace()
            gc.unfreeze()
            self.stats["compiles_in_window"] = self.compiles.count - before

    def read_memory(self) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.memory_peak = int(max(peaks))

    def check(self, name: str, value: float, limit: float) -> None:
        """One compared number and its limit; ``correct`` needs each at or
        under its limit."""
        self.checks.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim in self.checks)


def enable_cache() -> None:
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(run: Run, spec: dict) -> dict:
    dev = run.devices[0]
    out = {"correct": run.correct, "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": {}}
    for m in metrics_for(spec, run.cell["name"], bool(run.trace)):
        if run.trace:
            value = reader(m["name"])(run)
        elif m["name"] == "setup_s":
            value = run.setup_s
        else:
            value = run.e2e.get(m["name"])
        if value is not None:
            out["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(run.devices),
                     "memory_peak_bytes": run.memory_peak}
    if run.trace and run.summary is not None:
        s = run.summary
        out["device"].update(busy_s=s.busy_s, window_s=s.window_s)
        out["breakdown"] = {"device_ops": [[n, v] for n, v in s.top_ops()],
                            "idle_gaps": [[n, v] for n, v in s.top_gaps()]}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in run.checks}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    spec = load_spec()
    cell, config, traffic = resolve(spec, opts.workload)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devices = find_chips(int(cell["chips"]))
        peaks = peaks_for(devices[0].device_kind)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_cache()
    run = Run(cell, config, traffic, opts.seed, opts.seconds, opts.trace,
              devices, peaks, t_start)
    run.mark("python, JAX and the chip")
    driver(traffic["driver"]).run(run)
    run.check("compiles_in_window", run.stats["compiles_in_window"], 0)
    if run.trace:
        from bench.trace import summarize

        run.summary = summarize(str(TRACE_DIR))
    line = result_line(run, spec)
    print("set-up parts (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in run.setup_parts.items())
        + f"; programs built {run.compiles.count}: {run.compiles.hits} from "
        f"the cache, {run.compiles.misses} compiled", file=sys.stderr)
    for name, v, lim in run.checks:
        print(f"check {name}: {v:g} (limit {lim:g}) "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def entry(t_start: float) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main(t_start=t_start))
