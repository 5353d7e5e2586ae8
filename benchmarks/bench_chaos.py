"""Chaos / fault-tolerance benchmark: recovery time, checkpoint overhead,
and the bitwise-recovery gate — the measurement half of
``tests/test_fault_tolerance.py`` and ``tests/test_robustness.py``.

``--smoke`` (the CI acceptance run) does three things at Braille smoke
scale and writes ``BENCH_chaos.json``:

1. **bitwise gate** — SIGKILL a subprocess training run at a commit
   boundary, restart it until it exits clean, and require the final
   quantized weights to equal an uninterrupted run bit for bit;
2. **recovery time** — how long the restarted worker takes from process
   start to its first post-resume commit (restore + recompile + replay);
3. **checkpoint overhead** — per-commit wall time with checkpointing off /
   async / blocking at the smoke policy cadence, reported as p50/p99
   commit-stall milliseconds, added-ms-per-commit, and a samples-per-second
   overhead percentage.  The acceptance gate — async checkpointing costs
   **<10%** samples/s vs no checkpointing — enforces on real accelerator
   devices; on shared-CPU CI runners the number is recorded, not enforced
   (the repo-wide wall-clock-gate policy, see ``bench_braille --sharded``).

``--serve --smoke`` is the serving-path chaos drill (ISSUE 10): per
backend/quant config it runs a clean streaming baseline, then the same
workload under (a) malformed-stream fuzzing at the guard boundary, (b)
injected launch faults (lane restart + bit-exact session re-seat), and
(c) an overload storm against bounded shed queues — gating that healthy
sessions stay **bitwise equal** to the clean run, queue memory stays
bounded, and the engine never dies.  It also measures the clean-path
guard overhead (gated **<5%** samples/s on accelerator devices, recorded
on shared-CPU CI), and merges a ``"serve"`` section into the same
``BENCH_chaos.json``.

Usage:
    python -m benchmarks.bench_chaos --smoke [--out-dir .]
    python -m benchmarks.bench_chaos --serve --smoke [--out-dir .]
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Optional

import numpy as np

from repro.train import chaos

def merge_write(out_dir: Optional[str], updates: Dict) -> Optional[Path]:
    """Merge ``updates`` into ``BENCH_chaos.json`` (training smoke and the
    serve drill each own their top-level keys, so either can run alone
    without clobbering the other's section)."""
    if out_dir is None:
        return None
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    path = Path(out_dir) / "BENCH_chaos.json"
    payload: Dict = {"benchmark": "chaos", "schema": 1}
    if path.exists():
        try:
            payload = json.loads(path.read_text())
        except (json.JSONDecodeError, OSError):
            pass   # unreadable artifact: rewrite from scratch
    payload.update(updates)
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}")
    return path


SMOKE_KW = dict(epochs=4, samples_per_class=12, num_ticks=48, spb=16)
# Overhead is measured at the paper's operating point (256-tick Braille
# samples, BRAM batch depth 50) while the kill drill stays tiny for
# wall-clock; the 6 epochs give enough steady-state intervals for stable
# statistics.  every=2 is the smoke checkpoint cadence (one durable cut
# per 100 samples — still far hotter than any production policy).
OVERHEAD_KW = dict(epochs=6, samples_per_class=50, num_ticks=256, spb=50)
OVERHEAD_EVERY = 2
OVERHEAD_REPEATS = 3
OVERHEAD_GATE_PCT = 10.0


def measure_checkpoint_overhead(
    mode: str,
    ckpt_dir: Optional[str],
    checkpoint_every: int = OVERHEAD_EVERY,
    **kw,
) -> Dict[str, float]:
    """Per-commit wall-time stats for one checkpointing mode (one run).

    ``mode`` is ``"off"`` (no policy), ``"async"`` (saves queued to the
    background writer) or ``"sync"`` (blocking saves).  The timing hook
    blocks on the committed weights, so each interval between consecutive
    commits is the true commit stall — compute plus the save path that
    runs inside it.  Throughput comes from the mean of *intra-epoch*
    intervals (epoch boundaries carry offload/decode spikes that belong
    to the pipeline, not the checkpointer); p50/p99 commit-stall use all
    steady-state intervals.  Warm-up (jit compile) intervals are dropped.
    """
    import jax

    assert mode in ("off", "async", "sync"), mode
    learner, pipeline = chaos.build_learner(
        ckpt_dir if mode != "off" else None,
        async_save=(mode == "async"),
        checkpoint_every=checkpoint_every,
        **kw,
    )
    spb = learner.ctrl.samples_per_batch
    marks = []

    def hook(lrn, commits):
        jax.block_until_ready(lrn.weights)
        marks.append((time.perf_counter(), lrn.cursor.epoch))

    learner.fit(pipeline, on_commit=hook)
    if learner.ckpt is not None:
        learner.ckpt.wait()
    deltas = np.asarray([b[0] - a[0] for a, b in zip(marks, marks[1:])])[3:]
    clean = np.asarray(
        [b[0] - a[0] for a, b in zip(marks, marks[1:]) if a[1] == b[1]]
    )[3:]
    assert clean.size >= 5, "need a few steady-state commits to measure"
    return {
        "mode": mode,
        "commits": int(len(marks)),
        "checkpoint_every": int(checkpoint_every),
        "p50_commit_ms": float(np.percentile(deltas, 50) * 1e3),
        "p99_commit_ms": float(np.percentile(deltas, 99) * 1e3),
        "mean_commit_ms": float(np.mean(clean) * 1e3),
        "samples_per_s": float(spb / np.mean(clean)),
    }


def overhead_suite(
    ckpt_root: str,
    repeats: int = OVERHEAD_REPEATS,
    checkpoint_every: int = OVERHEAD_EVERY,
    **kw,
) -> Dict[str, Dict[str, float]]:
    """off/async/sync overhead, interleaved and best-of-``repeats``.

    Single-shot mode comparisons on a shared CPU carry large run-order
    noise (frequency/cache warm-up, co-tenant load); interleaving the
    modes and keeping each mode's best throughput cancels the drift.
    Returns ``{mode: stats}`` plus ``async_overhead_pct`` /
    ``sync_overhead_pct`` relative to ``off`` and the transferable
    ``*_added_ms_per_commit`` (overhead percentages shrink as the commit
    tile grows; the added milliseconds are what the checkpointer costs).
    """
    best: Dict[str, Dict[str, float]] = {}
    for rep in range(repeats):
        for mode in ("off", "async", "sync"):
            r = measure_checkpoint_overhead(
                mode, str(Path(ckpt_root) / f"{mode}{rep}"),
                checkpoint_every=checkpoint_every, **kw,
            )
            if (mode not in best
                    or r["samples_per_s"] > best[mode]["samples_per_s"]):
                best[mode] = r
    base = best["off"]["samples_per_s"]
    for mode in ("async", "sync"):
        best[f"{mode}_overhead_pct"] = 100.0 * (
            base - best[mode]["samples_per_s"]) / base
        best[f"{mode}_added_ms_per_commit"] = (
            best[mode]["mean_commit_ms"] - best["off"]["mean_commit_ms"])
    return best


def record_overhead_section() -> Dict[str, Dict[str, float]]:
    """Durability-cost record for the BENCH_train.json artifact: p50/p99
    commit-stall ms and samples/s with async saves on vs off, measured at
    the Braille smoke scale (the ISSUE-9 acceptance operating point) —
    ``bench_braille``/``bench_cue`` call this so the cost is tracked
    across PRs; the <10% gate itself runs in ``bench_chaos --smoke``."""
    print("== checkpoint overhead (Braille smoke scale, async writer vs off) ==")
    with tempfile.TemporaryDirectory() as d:
        suite = overhead_suite(d, **OVERHEAD_KW)
    for mode in ("off", "async", "sync"):
        r = suite[mode]
        print(f"  {mode:6s}: p50={r['p50_commit_ms']:7.2f}ms "
              f"p99={r['p99_commit_ms']:7.2f}ms "
              f"{r['samples_per_s']:8.1f} samples/s")
    print(f"  async overhead {suite['async_overhead_pct']:+.1f}% "
          f"(+{suite['async_added_ms_per_commit']:.2f}ms/commit), "
          f"blocking {suite['sync_overhead_pct']:+.1f}% "
          f"(gated <10% on accelerator devices by bench_chaos --smoke)")
    return suite


def smoke(out_dir: Optional[str] = None, seed: Optional[int] = None) -> Dict:
    rng = np.random.default_rng(seed)
    t0 = time.time()

    wargs = [
        "--epochs", SMOKE_KW["epochs"],
        "--samples-per-class", SMOKE_KW["samples_per_class"],
        "--ticks", SMOKE_KW["num_ticks"],
        "--spb", SMOKE_KW["spb"],
    ]
    # The golden and the drill both run in CPU-pinned workers, before this
    # process touches JAX: both sides of the bitwise gate share a platform.
    print("== golden (uninterrupted) run ==")
    with tempfile.TemporaryDirectory() as d:
        gold = chaos.golden_worker(
            str(Path(d) / "ck"), str(Path(d) / "golden"), wargs
        )

    print("== chaos drill: SIGKILL at a commit boundary, restart ==")
    kill_at = int(rng.integers(1, 6))
    with tempfile.TemporaryDirectory() as d:
        out = str(Path(d) / "result")
        res = chaos.run_chaos(
            str(Path(d) / "ck"), out, ["--kill-at-commit", kill_at], wargs
        )
        got = chaos.load_result_weights(out)
    bitwise_ok = sorted(got) == sorted(gold) and all(
        np.array_equal(got[k], gold[k]) for k in gold
    )
    print(f"  killed at commit {kill_at}, resumed from "
          f"{res['resumed_from']}, restarts={res['restarts']}, "
          f"recovery={res['recovery_s']:.2f}s, bitwise_ok={bitwise_ok}")

    print("== checkpoint overhead: off vs async vs blocking ==")
    with tempfile.TemporaryDirectory() as d:
        overhead = overhead_suite(d, **OVERHEAD_KW)
    for mode in ("off", "async", "sync"):
        r = overhead[mode]
        print(f"  {mode:6s}: p50={r['p50_commit_ms']:8.2f}ms "
              f"p99={r['p99_commit_ms']:8.2f}ms "
              f"{r['samples_per_s']:8.1f} samples/s")
    async_pct = overhead["async_overhead_pct"]
    sync_pct = overhead["sync_overhead_pct"]
    print(f"  async overhead {async_pct:+.1f}% "
          f"(+{overhead['async_added_ms_per_commit']:.2f}ms/commit), "
          f"blocking {sync_pct:+.1f}% "
          f"(+{overhead['sync_added_ms_per_commit']:.2f}ms/commit)")

    # The bitwise-recovery gate binds everywhere.  The <10% overhead gate
    # is wall-clock: per the repo's policy (bench_braille --sharded, the
    # bench_serve floors), wall-clock gates enforce on real accelerator
    # devices only — shared-CPU CI runners carry co-tenant load that
    # swings a ~1ms/commit cost by more than the gate width, so there the
    # number is measured and recorded, not enforced.
    import jax

    gate_enforced = jax.default_backend() != "cpu"
    overhead_ok = (not gate_enforced) or async_pct < OVERHEAD_GATE_PCT

    rc = 0 if (bitwise_ok and overhead_ok) else 1
    if gate_enforced:
        print(f"acceptance (bitwise recovery AND async ckpt overhead "
              f"<{OVERHEAD_GATE_PCT}%): {'PASS' if rc == 0 else 'FAIL'}")
    else:
        print(f"acceptance: overhead gate n/a (shared CPU host; recorded "
              f"async {async_pct:+.1f}%); bitwise recovery "
              f"{'PASS' if rc == 0 else 'FAIL'}")
    payload = {
        "kill_at_commit": kill_at,
        "resumed_from": res["resumed_from"],
        "restarts": res["restarts"],
        "recovery_s": res["recovery_s"],
        "bitwise_ok": bool(bitwise_ok),
        "checkpoint_overhead": overhead,
        "overhead_gate_pct": OVERHEAD_GATE_PCT,
        "overhead_gate_enforced": bool(gate_enforced),
        "async_overhead_pct": async_pct,
        "sync_overhead_pct": sync_pct,
        "wall_s": time.time() - t0,
        "rc": rc,
    }
    merge_write(out_dir, payload)
    return payload


# --------------------------------------------------------------------------
# serving-path chaos (ISSUE 10): fuzz, faults, overload against the engine
# --------------------------------------------------------------------------

SERVE_GUARD_GATE_PCT = 5.0
SERVE_CONFIGS = (("scan", False), ("scan", True), ("kernel", False))


def _serve_setup(seed: int, n: int = 6, ticks: int = 48, quantized=False):
    import jax

    from repro.core import aer
    from repro.core.rsnn import Presets, init_params

    cfg = Presets.braille(n_classes=3, num_ticks=ticks, quantized=quantized)
    params = init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        t = int(rng.integers(12, ticks + 1))
        raster = (rng.random((t, cfg.n_in)) < 0.25).astype(np.float32)
        ev = np.asarray(
            aer.encode_sample(raster, i % 3, label_tick=t // 4,
                              end_tick=t - 1),
            np.uint32,
        )
        reqs.append(ev[np.argsort(ev & aer.MAX_TICK, kind="stable")])
    return cfg, params, reqs, rng


def _stream_once(engine, reqs, abuse=None):
    """Run the streaming workload (two ragged feeds per session) and
    return the final logits per session; ``abuse(engine, handles, step)``
    injects hostile behaviour between feed rounds."""
    handles = [engine.open_session() for _ in reqs]
    for step in range(2):
        for h, ev in zip(handles, reqs):
            mid = len(ev) // 2
            h.feed(ev[:mid] if step == 0 else ev[mid:])
        if abuse is not None:
            abuse(engine, handles, step)
        engine.pump()
    engine.pump(drain=True)
    return [h.result() for h in handles]


def _bitwise_equal(got, want) -> bool:
    return len(got) == len(want) and all(
        g.status == w.status and g.pred == w.pred
        and np.array_equal(g.logits, w.logits)
        for g, w in zip(got, want)
    )


def _fuzz_words(rng, size: int) -> np.ndarray:
    """Raw 32-bit noise — virtually always malformed somewhere."""
    return rng.integers(0, 2**32, size=size, dtype=np.uint32)


def serve_chaos_config(backend: str, quantized: bool, seed: int) -> Dict:
    """One backend/quant config's serve drill: clean baseline, fuzz storm,
    injected launch faults, overload storm, guard overhead."""
    from repro.core.aer import AEREncodingError
    from repro.serve import BatchedEngine, OverloadError, ServeStatus

    cfg, params, reqs, rng = _serve_setup(seed, quantized=quantized)
    eng_kw = dict(backend=backend, max_batch=4, tick_tile=8)

    def engine(**kw):
        return BatchedEngine(cfg, params, **{**eng_kw, **kw})

    clean = _stream_once(engine(), reqs)

    # -- malformed-stream fuzzing: hostile feeds at the guard boundary.
    # Every rejection must be a typed AEREncodingError and the *same*
    # sessions' results must stay bitwise identical to the clean run.
    fuzz_stats = {"attempts": 0, "typed": 0}

    def fuzz_abuse(eng, handles, step):
        for _ in range(8):
            fuzz_stats["attempts"] += 1
            try:
                handles[0].feed(_fuzz_words(rng, int(rng.integers(1, 32))))
            except AEREncodingError:
                fuzz_stats["typed"] += 1
        try:
            eng.submit(_fuzz_words(rng, 16))
            fuzz_stats["attempts"] += 1
        except AEREncodingError:
            fuzz_stats["attempts"] += 1
            fuzz_stats["typed"] += 1

    eng = engine()
    fuzzed = _stream_once(eng, reqs, abuse=fuzz_abuse)
    fuzz_ok = (
        _bitwise_equal(fuzzed, clean)
        and fuzz_stats["typed"] == fuzz_stats["attempts"] > 0
    )

    # -- injected launch faults: every 3rd streaming launch dies; the lane
    # restarts (fresh backend, sessions re-seated from bit-exact eviction
    # snapshots) and final results must still match the clean run bitwise.
    count = [0]

    def flaky(model_id, kind):
        if kind != "stream":
            return
        count[0] += 1
        if count[0] % 3 == 0:
            raise RuntimeError(f"injected launch fault #{count[0]}")

    eng = BatchedEngine(cfg, params, fault_hook=flaky, **eng_kw)
    faulted = _stream_once(eng, reqs)
    restarts = eng.stream_stats(1.0).lane_restarts
    fault_ok = _bitwise_equal(faulted, clean) and restarts >= 1

    # -- overload storm: whole-sample serve() against a tiny bounded shed
    # queue.  Every submitted item must come back as a typed result
    # (OK | REJECTED), the queue must stay within its bound, and the
    # engine must serve cleanly afterwards (never dies).
    storm_reqs = []
    for i in range(24):
        t = 8 * (i % 5 + 1)
        raster = (rng.random((t, cfg.n_in)) < 0.25).astype(np.float32)
        from repro.core import aer
        ev = np.asarray(
            aer.encode_sample(raster, i % 3, label_tick=0, end_tick=t - 1),
            np.uint32,
        )
        storm_reqs.append(ev[np.argsort(ev & aer.MAX_TICK, kind="stable")])
    eng = BatchedEngine(
        cfg, params, backend=backend, max_batch=4, tick_granularity=8,
        max_pending=4, admission="shed", max_inflight_tiles=1,
    )
    res, stats = eng.serve(iter(storm_reqs))
    statuses = {r.status for r in res}
    bounded_ok = (
        len(res) == len(storm_reqs)
        and statuses <= {ServeStatus.OK, ServeStatus.REJECTED}
        and stats.shed > 0
        and eng.scheduler.pending <= 4
    )
    try:
        after, _ = eng.serve(iter(reqs[:2]))
        alive_ok = all(r.status is ServeStatus.OK for r in after)
    except Exception:
        alive_ok = False

    # Hard-reject policy: a full queue raises OverloadError at submit()
    # and admits nothing beyond the bound.
    eng = BatchedEngine(
        cfg, params, backend=backend, max_batch=8, max_pending=2,
    )
    rejected = 0
    for ev in storm_reqs[:6]:
        try:
            eng.submit(ev)
        except OverloadError:
            rejected += 1
    reject_ok = rejected == 4 and eng.scheduler.pending == 2

    ok = fuzz_ok and fault_ok and bounded_ok and alive_ok and reject_ok
    print(f"  {backend:6s} quant={str(quantized):5s}: "
          f"fuzz={'PASS' if fuzz_ok else 'FAIL'} "
          f"faults={'PASS' if fault_ok else 'FAIL'} "
          f"(restarts={restarts}) "
          f"overload={'PASS' if bounded_ok and reject_ok else 'FAIL'} "
          f"(shed={stats.shed}) alive={'PASS' if alive_ok else 'FAIL'}")
    return {
        "backend": backend,
        "quantized": bool(quantized),
        "fuzz_ok": bool(fuzz_ok),
        "fuzz_rejections": int(fuzz_stats["typed"]),
        "fault_ok": bool(fault_ok),
        "lane_restarts": int(restarts),
        "overload_ok": bool(bounded_ok and reject_ok),
        "shed": int(stats.shed),
        "alive_ok": bool(alive_ok),
        "ok": bool(ok),
    }


def measure_guard_overhead(seed: int, repeats: int = 3) -> Dict[str, float]:
    """Clean-path cost of input validation: whole-sample ``serve()``
    samples/s with the guard on vs ``guard=False``, best-of-``repeats``
    interleaved (same drift-cancelling policy as the checkpoint overhead
    suite)."""
    from repro.serve import BatchedEngine

    cfg, params, _, rng = _serve_setup(seed)
    reqs = []
    from repro.core import aer
    for i in range(64):
        t = int(rng.integers(12, 49))
        raster = (rng.random((t, cfg.n_in)) < 0.25).astype(np.float32)
        ev = np.asarray(
            aer.encode_sample(raster, i % 3, label_tick=0, end_tick=t - 1),
            np.uint32,
        )
        reqs.append(ev[np.argsort(ev & aer.MAX_TICK, kind="stable")])
    best = {}
    for _ in range(repeats):
        for mode, guard in (("on", None), ("off", False)):
            eng = BatchedEngine(
                cfg, params, backend="scan", max_batch=8, guard=guard
            )
            eng.warmup(48)
            _, stats = eng.serve(iter(reqs))
            sps = stats.samples_per_sec
            if mode not in best or sps > best[mode]:
                best[mode] = sps
    pct = 100.0 * (best["off"] - best["on"]) / best["off"]
    return {
        "samples_per_s_guard_on": float(best["on"]),
        "samples_per_s_guard_off": float(best["off"]),
        "guard_overhead_pct": float(pct),
    }


def serve_smoke(out_dir: Optional[str] = None, seed: Optional[int] = None) -> Dict:
    """The ``--serve --smoke`` acceptance drill; merges a ``"serve"``
    section into ``BENCH_chaos.json``."""
    import jax

    t0 = time.time()
    seed = 0 if seed is None else seed
    print("== serving chaos: fuzz / launch faults / overload ==")
    configs = [
        serve_chaos_config(be, q, seed) for be, q in SERVE_CONFIGS
    ]
    print("== clean-path guard overhead (scan backend) ==")
    overhead = measure_guard_overhead(seed)
    pct = overhead["guard_overhead_pct"]
    print(f"  guard on {overhead['samples_per_s_guard_on']:8.1f} samples/s, "
          f"off {overhead['samples_per_s_guard_off']:8.1f} samples/s "
          f"({pct:+.1f}%)")

    # Correctness gates (bitwise containment, bounded queues, liveness)
    # bind everywhere; the <5% guard-overhead gate is wall-clock and binds
    # on real accelerator devices only (repo policy, see smoke() above).
    gate_enforced = jax.default_backend() != "cpu"
    chaos_ok = all(c["ok"] for c in configs)
    overhead_ok = (not gate_enforced) or pct < SERVE_GUARD_GATE_PCT
    rc = 0 if (chaos_ok and overhead_ok) else 1
    if gate_enforced:
        print(f"acceptance (containment AND guard overhead "
              f"<{SERVE_GUARD_GATE_PCT}%): {'PASS' if rc == 0 else 'FAIL'}")
    else:
        print(f"acceptance: overhead gate n/a (shared CPU host; recorded "
              f"{pct:+.1f}%); containment "
              f"{'PASS' if chaos_ok else 'FAIL'}")
    section = {
        "configs": configs,
        "guard_overhead": overhead,
        "guard_gate_pct": SERVE_GUARD_GATE_PCT,
        "guard_gate_enforced": bool(gate_enforced),
        "chaos_ok": bool(chaos_ok),
        "wall_s": time.time() - t0,
        "rc": rc,
    }
    merge_write(out_dir, {"serve": section})
    return section


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: bitwise recovery + <10%% async overhead, "
                         "written to BENCH_chaos.json")
    ap.add_argument("--serve", action="store_true",
                    help="serving-path chaos drill: fuzz/fault/overload "
                         "containment + <5%% guard overhead, merged into "
                         "BENCH_chaos.json")
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--seed", type=int, default=None,
                    help="fix the randomized kill commit / serve fuzz seed")
    opts = ap.parse_args(argv)
    if not opts.smoke:
        ap.error("pass --smoke (optionally with --serve for the "
                 "serving-path drill)")
    if opts.serve:
        return serve_smoke(out_dir=opts.out_dir, seed=opts.seed)["rc"]
    return smoke(out_dir=opts.out_dir, seed=opts.seed)["rc"]


if __name__ == "__main__":
    sys.exit(main())
