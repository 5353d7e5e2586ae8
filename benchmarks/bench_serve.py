"""Batched serving vs the sequential controller loop (ISSUE 1 tentpole bench).

Measures end-to-end classification throughput on the Braille config:

* **sequential** — the FSM-faithful baseline: one sample at a time through
  the jit'd single-sample inference entry
  (:func:`repro.core.controller.make_infer_fn`), host-decoded per request —
  how the chip serves its AER bus;
* **batched**    — :class:`repro.serve.BatchedEngine`: requests bucketed by
  tick length, padded into batch tiles, one jit'd forward per tile shape.

Reports samples/sec for both, the speedup (acceptance: ≥ 4× at batch ≥ 32),
and the batched path's p50/p99 request latency.  Compile time is excluded
from both sides via warmup.  A ragged-stream mode exercises the bucketing
scheduler with mixed tick lengths.

``--streaming`` switches to the stateful session path (ISSUE 6 tentpole
gate): N concurrent sessions fed their AER streams in interleaved
increments through ``open_session()/feed()/pump()``, with carry state
resident in the device session pool.  Reports events/s, session-ticks/s and
p50/p99 tick-tile latency, spot-checks a sample of sessions bitwise against
the whole-sample path, and records everything under the ``"streaming"`` key
of ``BENCH_serve.json``.  The full run drives ≥ 10k concurrent sessions on
CPU; ``--smoke`` shrinks the fleet for the CI lanes (correctness always
gates; the throughput floor only on the single-device lane).

    PYTHONPATH=src python -m benchmarks.bench_serve [--fast] [--batch 64]
    PYTHONPATH=src python -m benchmarks.bench_serve --streaming [--sessions N]
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import aer
from repro.core.controller import make_infer_fn
from repro.core.rsnn import Presets, init_params, trainable
from repro.data.braille import BrailleConfig, make_braille_dataset
from repro.data.cue import CueConfig, make_cue_dataset
from repro.data.pipeline import EventStream
from repro.serve import BatchedEngine, ModelRegistry
from repro.serve.batching import decode_events_host, request_ticks

REPS = 3   # best-of-N measurement passes (noisy shared-CPU containers)


def _ragged_stream(base_stream, num_ticks, seed=0):
    """Re-encode each sample truncated to a random length — mixed-tick
    traffic for the bucketing scheduler."""
    rng = np.random.default_rng(seed)
    out = []
    for ev in base_stream:
        t = int(rng.integers(num_ticks // 2, num_ticks + 1))
        kind = np.asarray(ev, np.uint32) >> 24
        ticks = np.asarray(ev, np.uint32) & aer.MAX_TICK
        keep = (ticks < t) | (kind == aer.EVT_LABEL)
        words = np.asarray(ev, np.uint32)[keep & (kind != aer.EVT_END)]
        words = np.minimum(words, (words & ~np.uint32(aer.MAX_TICK)) | (t - 1))
        end = np.uint32((aer.EVT_END << 24) | (t - 1))
        out.append(np.concatenate([words, [end]]))
    return out


def run_sequential(cfg, weights, stream):
    infer = make_infer_fn(cfg)
    # pre-compile every tick-length the stream contains (steady-state timing,
    # same treatment the batched side gets)
    for ticks in sorted({request_ticks(ev) for ev in stream}):
        r, v, _ = decode_events_host([stream[0]], cfg.n_in, ticks, cfg.label_delay)
        jax.block_until_ready(infer(weights, r[:, 0], v[:, 0])["acc_y"])

    best_wall, preds = float("inf"), []
    for _ in range(REPS):  # best-of-N: the container CPU is noisy
        run = []
        t0 = time.perf_counter()
        for ev in stream:
            ticks = request_ticks(ev)
            raster, valid, _ = decode_events_host([ev], cfg.n_in, ticks, cfg.label_delay)
            out = infer(weights, raster[:, 0], valid[:, 0])
            run.append(int(jax.block_until_ready(out["pred"])))
        wall = time.perf_counter() - t0
        if wall < best_wall:
            best_wall, preds = wall, run
    return preds, len(stream) / best_wall, best_wall


def run_batched(cfg, params, stream, batch, granularity=32, mesh=None):
    eng = BatchedEngine(
        cfg, params, backend="auto", max_batch=batch,
        tick_granularity=granularity, mesh=mesh,
    )
    eng.serve(iter(stream))      # warm pass: compiles every tile shape
    best = None
    for _ in range(REPS):        # best-of-N steady-state pass
        results, stats = eng.serve(iter(stream))
        if best is None or stats.wall_s < best[1].wall_s:
            best = (results, stats)
    return best


def run_streaming(cfg, params, stream, n_sessions, batch, tick_tile,
                  phases=4, spot_check=64, seed=0, mesh=None):
    """Drive ``n_sessions`` concurrent stateful sessions through the
    continuous-batching pump, feeding each stream in ``phases`` interleaved
    increments (the adversarial arrival pattern: no session ever has its
    whole sample available at once)."""
    from repro.serve.batching import max_sessions_for

    # Every session must be resident at once — the gate is *concurrent*
    # sessions, so size the pool to the fleet (and report its byte cost).
    capacity = max(n_sessions, max_sessions_for(cfg))
    eng = BatchedEngine(
        cfg, params, backend="auto", max_batch=batch,
        max_sessions=capacity, tick_tile=tick_tile, mesh=mesh,
    )
    rng = np.random.default_rng(seed)
    bufs = []
    for i in range(n_sessions):
        ev = np.asarray(stream[i % len(stream)], np.uint32)
        bufs.append(ev[np.argsort(ev & aer.MAX_TICK, kind="stable")])
    cuts = [np.linspace(0, len(ev), phases + 1).astype(int) for ev in bufs]

    # warm pass compiles the tile shapes the fleet will hit
    warm = [eng.open_session() for _ in range(min(batch, n_sessions))]
    for h, ev in zip(warm, bufs):
        h.feed(ev)
    eng.pump(drain=True)
    for h in warm:
        h.result()

    eng.reset_stream_stats()
    t0 = time.perf_counter()
    handles = [eng.open_session() for _ in range(n_sessions)]
    for p in range(phases):
        for h, ev, c in zip(handles, bufs, cuts):
            h.feed(ev[c[p]:c[p + 1]])
        eng.pump()
    eng.pump(drain=True)
    snaps = [h.result() for h in handles]
    wall = time.perf_counter() - t0
    stats = eng.stream_stats(wall)

    # correctness spot check: a sample of sessions vs the whole-sample path
    idx = rng.choice(n_sessions, size=min(spot_check, n_sessions),
                     replace=False)
    ref_eng = BatchedEngine(cfg, params, backend="auto", max_batch=batch,
                            mesh=mesh)
    ref, _ = ref_eng.serve(iter([bufs[i] for i in idx]))
    mism = sum(
        int(not np.array_equal(np.asarray(r.logits), snaps[i].logits))
        for r, i in zip(ref, idx)
    )
    return snaps, stats, eng, mism, len(idx)


# Throughput floor for the single-device CI smoke lane (events/s).  Set an
# order of magnitude under what the container CPU sustains (~55k events/s at
# 1024 sessions) so the gate only trips on real regressions (a serialized
# pump, a per-session launch), not machine noise.
STREAM_SMOKE_FLOOR_EPS = 5_000.0


def main_streaming(opts):
    import os

    num_ticks = 64
    n_sessions = opts.sessions or (1024 if opts.fast else 10_000)
    cfg = Presets.braille(n_classes=3, num_ticks=num_ticks)
    params = init_params(jax.random.key(0), cfg)
    data = make_braille_dataset(
        "AEU", BrailleConfig(num_ticks=num_ticks, samples_per_class=32)
    )
    stream = list(EventStream(data, "train"))
    tick_tile = opts.tick_tile or None

    mesh = None
    if opts.sharded:
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh()
        print(f"sharded streaming over {len(jax.devices())} device(s)")
    print(f"streaming sessions: {n_sessions} concurrent  "
          f"batch={opts.batch}  tick_tile={tick_tile or 'drain'}  "
          f"T={num_ticks}")
    snaps, stats, eng, mism, checked = run_streaming(
        cfg, params, stream, n_sessions, opts.batch, tick_tile, mesh=mesh
    )
    pool_bytes = eng.pool.state_bytes()
    print(f"events    : {stats.events:9d} consumed   "
          f"{stats.events_per_sec:12.1f} events/s")
    print(f"ticks     : {stats.ticks:9d} advanced   "
          f"{stats.ticks_per_sec:12.1f} session-ticks/s")
    print(f"tiles     : {stats.tiles:9d} launched   "
          f"mean lanes {stats.mean_lanes:.1f}  "
          f"{stats.compiled_shapes} shapes")
    print(f"tile latency: p50={stats.p50_tile_latency_s*1e3:.2f} ms  "
          f"p99={stats.p99_tile_latency_s*1e3:.2f} ms")
    print(f"pool      : {len(eng.pool._free) + len(eng.pool._resident)} slots "
          f"({pool_bytes/2**20:.1f} MiB)  evictions={stats.evictions}  "
          f"readmissions={stats.readmissions}")
    print(f"correctness: {checked - mism}/{checked} spot-checked sessions "
          f"bitwise equal to the whole-sample path")

    summary = {
        "sessions": n_sessions,
        "batch": opts.batch,
        "tick_tile": opts.tick_tile or None,
        "events": stats.events,
        "events_per_sec": stats.events_per_sec,
        "ticks_per_sec": stats.ticks_per_sec,
        "tiles": stats.tiles,
        "mean_lanes": stats.mean_lanes,
        "p50_tile_latency_s": stats.p50_tile_latency_s,
        "p99_tile_latency_s": stats.p99_tile_latency_s,
        "compiled_shapes": stats.compiled_shapes,
        "evictions": stats.evictions,
        "readmissions": stats.readmissions,
        "pool_bytes": pool_bytes,
        "wall_s": stats.wall_s,
        "spot_checked": checked,
        "mismatches": mism,
    }
    if opts.out_dir:
        out_dir = Path(opts.out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        out = out_dir / "BENCH_serve.json"
        payload = {"schema": 1, "benchmark": "batched_serving",
                   "jax_backend": jax.default_backend()}
        if out.exists():     # merge alongside the whole-sample numbers
            payload = json.loads(out.read_text())
        payload["streaming"] = summary
        out.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
        print(f"wrote {out}")

    # Virtual CPU devices oversubscribe the physical cores — the
    # 8-virtual-device lane gates correctness only, like the sharded serve.
    virtual = len(jax.devices()) > 1 and jax.default_backend() == "cpu"
    ok = mism == 0
    if opts.fast and not virtual:
        ok = ok and stats.events_per_sec >= STREAM_SMOKE_FLOOR_EPS
        print(f"acceptance (bitwise correctness, ≥ "
              f"{STREAM_SMOKE_FLOOR_EPS:.0f} events/s): "
              f"{'PASS' if ok else 'FAIL'}")
    else:
        why = (f"{len(jax.devices())} virtual CPU devices on "
               f"{os.cpu_count()} cores" if virtual else "full run")
        print(f"acceptance: throughput floor n/a ({why}) "
              f"(outputs match: {'yes' if mism == 0 else 'NO'})")
    return {"rc": 0 if ok else 1, "streaming": summary}


def main_multi_model(opts):
    """Multi-model serving smoke (ISSUE 8): Braille + cue registered in one
    :class:`~repro.serve.ModelRegistry`, served concurrently from one
    :class:`~repro.serve.BatchedEngine` over a mixed ``(events, model_id)``
    stream.  Gates bitwise equality of every per-model result against two
    dedicated single-model engines, and records per-model throughput under
    the ``"multi_model"`` key of ``BENCH_serve.json``."""
    num_ticks = 128
    n_req = 48 if opts.fast else 256    # per model
    cfg_b = Presets.braille(n_classes=3, num_ticks=num_ticks)
    params_b = init_params(jax.random.key(0), cfg_b)
    ccfg = CueConfig()
    cfg_c = Presets.cue_accumulation(num_ticks=ccfg.num_ticks)
    params_c = init_params(jax.random.key(1), cfg_c)

    data_b = make_braille_dataset(
        "AEU", BrailleConfig(num_ticks=num_ticks,
                             samples_per_class=max(2, n_req // 3))
    )
    stream_b = list(EventStream(data_b, "train"))[:n_req]
    data_c = make_cue_dataset(n_req, 2, cfg=ccfg)
    stream_c = list(EventStream(data_c, "train"))[:n_req]

    registry = ModelRegistry()
    registry.register("braille", cfg_b, params_b, backend="auto")
    registry.register("cue", cfg_c, params_c, backend="auto")
    eng = BatchedEngine(registry=registry, max_batch=opts.batch)

    # interleaved mixed-model traffic: requests alternate model per arrival
    mixed = []
    for evb, evc in zip(stream_b, stream_c):
        mixed.append((evb, "braille"))
        mixed.append((evc, "cue"))

    print(f"multi-model serving: braille(T={num_ticks}) + cue(T={ccfg.num_ticks}) "
          f"— {len(mixed)} mixed requests, batch={opts.batch}")
    eng.serve(iter(mixed))       # warm pass: compiles every tile shape
    best = None
    for _ in range(REPS):
        results, stats = eng.serve(iter(mixed))
        if best is None or stats.wall_s < best[1].wall_s:
            best = (results, stats)
    results, stats = best
    per = stats.per_model or {}
    for mid in ("braille", "cue"):
        s = per.get(mid)
        if s:
            print(f"  {mid:8s}: {s.requests:4d} requests  "
                  f"{s.samples_per_sec:9.1f} samples/s  {s.batches} tiles  "
                  f"p99={s.p99_latency_s*1e3:.2f} ms")

    # bitwise gate: per-model results vs two dedicated single-model engines
    ded_b = BatchedEngine(cfg_b, params_b, backend="auto",
                          max_batch=opts.batch)
    ded_c = BatchedEngine(cfg_c, params_c, backend="auto",
                          max_batch=opts.batch)
    ref_b, _ = ded_b.serve(iter(stream_b))
    ref_c, _ = ded_c.serve(iter(stream_c))
    mism = 0
    for mid, refs in (("braille", ref_b), ("cue", ref_c)):
        got = [r for r in results if r.model_id == mid]
        for g, r in zip(got, refs):
            if not np.array_equal(np.asarray(g.logits), np.asarray(r.logits)):
                mism += 1
    print(f"correctness: {len(results) - mism}/{len(results)} mixed-engine "
          f"results bitwise equal to the dedicated single-model engines")

    summary = {
        "requests": len(results),
        "batch": opts.batch,
        "models": {
            mid: {
                "requests": s.requests,
                "samples_per_sec": s.samples_per_sec,
                "batches": s.batches,
                "p50_latency_s": s.p50_latency_s,
                "p99_latency_s": s.p99_latency_s,
                "compiled_shapes": s.compiled_shapes,
            }
            for mid, s in per.items()
        },
        "samples_per_sec": stats.samples_per_sec,
        "compiled_shapes": stats.compiled_shapes,
        "mismatches": mism,
    }
    if opts.out_dir:
        out_dir = Path(opts.out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        out = out_dir / "BENCH_serve.json"
        payload = {"schema": 1, "benchmark": "batched_serving",
                   "jax_backend": jax.default_backend()}
        if out.exists():     # merge alongside the other serving sections
            payload = json.loads(out.read_text())
        payload["multi_model"] = summary
        out.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
        print(f"wrote {out}")
    ok = mism == 0
    print(f"acceptance (per-model results bitwise equal to dedicated "
          f"engines): {'PASS' if ok else 'FAIL'}")
    return {"rc": 0 if ok else 1, "multi_model": summary}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="fewer requests")
    ap.add_argument("--smoke", action="store_true",
                    help="alias for --fast (the CI smoke lanes)")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ragged", action="store_true",
                    help="mixed tick lengths (exercises bucketing)")
    ap.add_argument("--sharded", action="store_true",
                    help="serve through a data mesh over every visible "
                         "device (admission scales with device count)")
    ap.add_argument("--streaming", action="store_true",
                    help="stateful session streaming instead of the "
                         "whole-sample comparison")
    ap.add_argument("--multi-model", action="store_true",
                    help="Braille + cue registered in one engine, served "
                         "over a mixed stream (bitwise-gated vs dedicated "
                         "engines; per-model throughput recorded)")
    ap.add_argument("--sessions", type=int, default=0,
                    help="concurrent sessions for --streaming "
                         "(default 10000, or 1024 under --smoke/--fast)")
    ap.add_argument("--tick-tile", type=int, default=0,
                    help="fixed streaming tile tick length (0 = throughput "
                         "mode: each tile drains what its sessions have)")
    ap.add_argument("--out-dir", default="",
                    help="also write BENCH_serve.json here")
    opts = ap.parse_args(argv)
    opts.fast = opts.fast or opts.smoke

    if opts.streaming:
        return main_streaming(opts)
    if opts.multi_model:
        return main_multi_model(opts)

    num_ticks = 128
    n_req = 128 if opts.fast else 512
    cfg = Presets.braille(n_classes=3, num_ticks=num_ticks)
    params = init_params(jax.random.key(0), cfg)
    weights = trainable(params)

    per_class = max(2, n_req // 3)
    data = make_braille_dataset(
        "AEU", BrailleConfig(num_ticks=num_ticks, samples_per_class=per_class)
    )
    stream = list(EventStream(data, "train"))[:n_req]
    if opts.ragged:
        stream = _ragged_stream(stream, num_ticks)

    print(f"braille config: n_in={cfg.n_in} n_hid={cfg.n_hid} n_out={cfg.n_out} "
          f"T={num_ticks}  requests={len(stream)}  batch={opts.batch}")

    seq_preds, seq_sps, seq_wall = run_sequential(cfg, weights, stream)
    print(f"sequential controller loop : {seq_sps:9.1f} samples/s  "
          f"({seq_wall*1e3:8.1f} ms wall)")

    mesh = None
    if opts.sharded:
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh()
        print(f"sharded serving over {len(jax.devices())} device(s)")
    results, stats = run_batched(cfg, params, stream, opts.batch, mesh=mesh)
    print(f"batched engine (B≤{opts.batch:3d})   : {stats.samples_per_sec:9.1f} samples/s  "
          f"({stats.wall_s*1e3:8.1f} ms wall, {stats.batches} tiles, "
          f"{stats.compiled_shapes} shapes)")
    print(f"request latency            : p50={stats.p50_latency_s*1e3:.2f} ms  "
          f"p99={stats.p99_latency_s*1e3:.2f} ms  mean_batch={stats.mean_batch:.1f}")

    speedup = stats.samples_per_sec / seq_sps
    mism = sum(int(a != b.pred) for a, b in zip(seq_preds, results))
    print(f"speedup: {speedup:.1f}x   prediction mismatches vs sequential: "
          f"{mism}/{len(stream)}")
    # machine-readable summary for benchmarks/run.py → BENCH_serve.json
    summary = {
        "requests": len(stream),
        "batch": opts.batch,
        "num_devices": len(jax.devices()) if opts.sharded else 1,
        "samples_per_sec": stats.samples_per_sec,
        "sequential_samples_per_sec": seq_sps,
        "speedup": speedup,
        "p50_latency_s": stats.p50_latency_s,
        "p99_latency_s": stats.p99_latency_s,
        "mean_batch": stats.mean_batch,
        "compiled_shapes": stats.compiled_shapes,
        "mismatches": mism,
    }
    if opts.out_dir:
        out = Path(opts.out_dir) / "BENCH_serve.json"
        out.write_text(json.dumps(
            {"schema": 1, "benchmark": "batched_serving",
             "jax_backend": jax.default_backend(), **summary},
            indent=2, sort_keys=True) + "\n")
        print(f"wrote {out}")
    import os

    # virtual CPU devices are never the wall-clock target (they share the
    # host cores regardless of count) — the single-device lane gates speedup
    virtual_devices = opts.sharded and jax.default_backend() == "cpu"
    if opts.batch < 32 or virtual_devices:
        # the ≥4x bar is defined for batch ≥ 32 on comparable hardware;
        # smaller tiles are latency-oriented configurations, and virtual CPU
        # devices oversubscribing the physical cores make wall-clock
        # speedup meaningless (the single-device lane gates throughput) —
        # the sharded run still gates correctness per request
        why = (f"batch {opts.batch} < 32" if opts.batch < 32 else
               f"{len(jax.devices())} virtual CPU devices on "
               f"{os.cpu_count()} cores")
        print(f"acceptance: speedup gate n/a ({why}) "
              f"(outputs match: {'yes' if mism == 0 else 'NO'})")
        return {"rc": 0 if mism == 0 else 1, "serve": summary}
    status = "PASS" if (speedup >= 4.0 and mism == 0) else "FAIL"
    print(f"acceptance (≥4x at batch ≥ 32, outputs match): {status}")
    return {"rc": 0 if status == "PASS" else 1, "serve": summary}


if __name__ == "__main__":
    import sys

    out = main()
    sys.exit(out["rc"] if isinstance(out, dict) else out)
