"""Whole-sample serving: ``BatchedEngine.serve`` through the
``BucketingScheduler``, one seeded Braille character per request.

Each request is one character from a seeded pool (``character_pool`` slides
of the copied Braille generator), its raster cut to a length drawn
log-uniform in ``request_ticks``, encoded as the SoC's AER buffer (label
word at tick 0, so the readout accumulates over every tick of the request;
END word at its last tick).  All of it is drawn in set-up.

``loop: open``: requests arrive on a schedule drawn from the seed.  Bursts
start at uniform (Poisson-conditioned) times; each comes from a client
drawn from a Zipf law over ``clients`` and holds a geometric number of
characters (mean ``burst_requests_mean``), each falling due once it has
been sensed at ``request_ticks_per_s``.  A client sends one burst at a time.
The number of bursts is scaled until the schedule offers
``offered_events_per_s`` within 1%.

The window replays the schedule: each pass hands the requests that have
come due to ``engine.serve`` — which admits them into the scheduler's tick
buckets, launches a tile per bucket, and returns their results — and
records each request's latency from its due time to that return (the poll
that sees its result).  Set-up draws the schedule before it encodes the
requests, and encodes only those the schedule uses; then it serves, for
each padded width the window can produce, one call holding that many
requests of every bucket length, so nothing compiles inside the window.

Compared: every answered request's accumulated readout against the
integer reference (``bench/reference.py``) exactly, and the requests that
failed or were refused (limit 0).
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench import events as ev
from bench.harness import Run, subseed


class Requests:
    """The seeded requests: characters, lengths and event counts of half
    again as many as ``seconds`` of the offered rate needs, and the AER
    buffers of those the schedule uses (:meth:`encode_first`)."""

    def __init__(self, rng, tr: dict, T: int, seconds: float):
        rasters, labels = ev.braille_characters(
            rng, tr["letters"], tr["character_pool"],
            ev.BrailleParams(num_ticks=T))
        self.rasters, self.labels = rasters, labels
        lo, hi = tr["request_ticks"]
        per_tick = rasters.sum() / (rasters.shape[0] * T)
        mean_ticks = (hi - lo) / math.log(hi / lo)
        n = int(1.5 * tr["offered_events_per_s"] * seconds
                / (per_tick * mean_ticks)) + 256
        self.char = rng.integers(0, len(rasters), n)
        u = rng.random(n)
        self.ticks = np.clip(np.rint(np.exp(
            math.log(lo) + u * (math.log(hi) - math.log(lo)))), lo, hi
        ).astype(np.int64)
        # spikes of each character up to and including tick t
        self._spikes = rasters.sum(axis=2, dtype=np.int64).cumsum(axis=1)
        self.events = self._spikes[self.char, self.ticks - 1]
        self.bufs: list = []

    def encode(self, c: int, ticks: int) -> np.ndarray:
        return ev.encode_sample(self.rasters[c, :ticks], int(self.labels[c]),
                                0, ticks - 1)

    def encode_first(self, m: int) -> None:
        """Buffers of requests ``0 .. m-1``, equal to :meth:`encode`'s.  A
        character's whole image is tick-sorted, its label word after the
        tick-0 spikes: a request's buffer is the image's words up to its
        last tick, then an END word there."""
        T = self.rasters.shape[1]
        images = [self.encode(c, T)[:-1] for c in range(len(self.rasters))]
        keep = 1 + self._spikes[self.char[:m], self.ticks[:m] - 1]
        end = ev.word(ev.EVT_END, 0, self.ticks[:m] - 1)
        self.bufs = [np.concatenate([images[c][:k], end[q:q + 1]])
                     for q, (c, k) in enumerate(zip(self.char[:m], keep))]

    def raster(self, idx: np.ndarray) -> np.ndarray:
        """``(T_max, len(idx), n_in)`` rasters of requests ``idx``, zero past
        each request's end."""
        T = int(self.ticks[idx].max())
        out = np.zeros((T, len(idx), self.rasters.shape[2]), np.int8)
        for j, q in enumerate(idx):
            t = self.ticks[q]
            out[:t, j] = self.rasters[self.char[q], :t]
        return out


def schedule(seed: int, req: Requests, tr: dict, seconds: float):
    """``(due, idx)``: due times, sorted, and the request that falls due at
    each; bursts of a client never overlap."""
    target = tr["offered_events_per_s"]
    mean = tr["burst_requests_mean"]
    n_bursts = max(1, int(round(target * seconds / (mean * req.events.mean()))))
    for _ in range(8):
        due, idx = _bursts(np.random.default_rng(seed), req, tr, seconds,
                           n_bursts)
        offered = req.events[idx].sum() / seconds
        if abs(offered / target - 1.0) < 0.01:
            break
        n_bursts = max(1, int(round(n_bursts * target / max(offered, 1.0))))
    return due, idx


def _bursts(rng, req: Requests, tr: dict, seconds: float, n_bursts: int):
    n_clients = tr["clients"]
    starts = np.sort(rng.uniform(0.0, seconds, n_bursts))
    ranks = np.arange(1, n_clients + 1, dtype=np.float64) ** -tr["zipf_s"]
    who = rng.choice(n_clients, n_bursts, p=ranks / ranks.sum())
    size = rng.geometric(1.0 / tr["burst_requests_mean"], n_bursts)
    tick_s = 1.0 / tr["request_ticks_per_s"]
    free = [0.0] * n_clients
    ticks = req.ticks.tolist()
    due = []
    q = 0
    for t0, c, k in zip(starts.tolist(), who.tolist(), size.tolist()):
        t = max(t0, free[c])
        for _ in range(k):
            if q >= len(ticks):
                raise ValueError("the schedule needs more requests than "
                                 "set-up drew")
            t += ticks[q] * tick_s
            if t >= seconds:
                break
            due.append(t)
            q += 1
        free[c] = t
    due = np.asarray(due)
    order = np.argsort(due, kind="stable")
    return due[order], order


def _build(run: Run):
    import jax.numpy as jnp
    from repro.serve import BatchedEngine

    from bench.model import make_weights, rsnn_config

    c, tr = run.config, run.traffic
    run.mark("importing the program")
    rng = np.random.default_rng(subseed(run.seed, 1))
    req = Requests(rng, tr, c["sample_ticks"], run.seconds)
    run.mark("drawing the requests")
    weights = make_weights(c, subseed(run.seed, 2))
    cfg = rsnn_config(c, c["sample_ticks"])
    engine = BatchedEngine(cfg, dict(weights, alpha=jnp.float32(cfg.neuron.alpha)),
                           tick_granularity=tr["tick_granularity"])
    jnp.zeros(()).block_until_ready()
    run.mark("weights and engine")
    return req, weights, engine


def _warm(run: Run, engine, req: Requests, tr: dict) -> None:
    """Serve, for each padded width the window can produce, one call that
    holds that many requests of every bucket length: one tile of each
    (bucket length, width)."""
    from repro.serve import batching

    lo, hi = tr["request_ticks"]
    g = tr["tick_granularity"]
    lengths = sorted({batching.bucket_ticks(t, g) for t in range(lo, hi + 1)})
    bufs = [req.encode(0, min(T, hi)) for T in lengths]
    width = 1
    while True:
        engine.serve([b for b in bufs for _ in range(width)])
        if width >= engine.max_batch:
            break
        width = min(2 * width, engine.max_batch)
    run.mark("warming the buckets and widths")


def run(run: Run) -> None:
    from repro.serve import ServeStatus

    tr = run.traffic
    req, weights, engine = _build(run)
    due, idx = schedule(subseed(run.seed, 3), req, tr, run.seconds)
    m = len(due)
    run.stats["offered_events_per_s"] = float(req.events[idx].sum()) / run.seconds
    req.encode_first(m)
    run.mark("drawing the schedule and encoding its requests")
    _warm(run, engine, req, tr)

    lat = np.full(m, np.inf)
    lag = np.zeros(m)
    ok = np.zeros(m, bool)
    logits = np.zeros((m, run.config["n_out"]))
    serve_s = 0.0
    calls = batches = served_ok = 0
    with run.window():
        t0 = time.perf_counter()
        i = 0
        while i < m:
            now = time.perf_counter() - t0
            j = int(np.searchsorted(due, now, side="right"))
            if j == i:
                time.sleep(min(max(due[i] - now, 0.0), 1e-3))
                continue
            lag[i:j] = now - due[i:j]
            a = time.perf_counter()
            with run.span("serve"):
                results, stats = engine.serve([req.bufs[q] for q in idx[i:j]])
            b = time.perf_counter()
            lat[i:j] = b - t0 - due[i:j]
            for k, r in enumerate(results):
                ok[i + k] = r.status is ServeStatus.OK
                logits[i + k] = r.logits
            serve_s += b - a
            calls += 1
            batches += stats.batches
            served_ok += int(ok[i:j].sum())
            i = j
    run.attempted = m
    run.failed = int((~ok).sum())
    run.e2e["result_latency_p95_ms"] = 1e3 * float(np.percentile(lat, 95))
    run.e2e["served_events_per_s"] = float(req.events[idx][ok].sum()) / run.window_s
    run.stats.update(
        requests=m, serve_calls=calls, batches=batches, serve_s=serve_s,
        mean_batch=served_ok / batches if batches else 0.0,
        gen_lag_p95_ms=1e3 * float(np.percentile(lag, 95)))
    run.read_memory()
    from bench.reference import Datapath

    run.evidence = {"latency_s": lat, "due_s": due, "requests": req,
                    "order": idx, "weights": weights, "logits": logits,
                    "ok": ok}
    compare(run, Datapath.from_config(run.config), weights, req, idx, logits,
            ok)


def compare(run: Run, dp, weights, req: Requests, order, logits, ok) -> None:
    """Every answered request's readout against the reference over its own
    ticks, in blocks of requests; ``logits[i]``/``ok[i]`` are the program's
    (or a stand-in's) answers to request ``order[i]``."""
    from bench.reference import run_streams

    w = {k: np.asarray(v) for k, v in weights.items()}
    bad = 0
    block = 2048
    for b0 in range(0, len(order), block):
        pos = np.arange(b0, min(b0 + block, len(order)))
        pos = pos[ok[pos]]
        if len(pos) == 0:
            continue
        q = order[pos]
        ref = run_streams(dp, w, req.raster(q), req.ticks[q])
        bad += int((logits[pos] != ref["acc_y"]).sum())
    run.check("readout_mismatches", bad, 0)
    run.check("failed_requests", run.failed, 0)
