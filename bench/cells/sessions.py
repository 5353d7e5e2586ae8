"""Streaming sessions: a fleet of open sessions fed Braille slides.

The window drives ``SessionHandle.feed`` -> ``BatchedEngine.pump`` ->
``poll`` through the guard, the packer and decode, the pool's gather and
scatter, the ``rsnn_step_sessions`` kernel and the harvest.

All traffic is drawn in set-up (:class:`Streams`, :func:`schedule`): each
session's stream is a concatenation of seeded Braille characters, cut into
feeds of ``feed_ticks`` (log-uniform, never across a character), held as
views into a few shared word tables.  The window only replays it.

``loop: closed`` (saturated): sessions are fed in blocks of ``feed_block``;
a session gets its next feed once the engine has taken all of its previous
one (every ``pump`` drains what is processable), so tiles stay full.
``loop: open`` (fixed rate): feeds arrive on a schedule drawn from the seed:
bursts (a fingertip slides over a few characters at ``session_ticks_per_s``,
then rests) at Poisson times, on sessions drawn from a Zipf law.

Window edges: the window closes when every launched tile has been harvested
(``pump(drain=True)``), and its length counts that drain.  Results come from
the harvested snapshots (``poll``), which say how many ticks of each stream
were processed.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench import events as ev
from bench.harness import Run, subseed


class Streams:
    """Per-session streams and their feeds, pre-drawn from one generator."""

    def __init__(self, rng, n: int, chars: int, pool: int, letters, T: int,
                 feed_ticks):
        rasters, labels = ev.braille_characters(
            rng, letters, pool, ev.BrailleParams(num_ticks=T))
        self.T, self.K, self.n = T, chars, n
        self.rasters = rasters                              # (pool, T, n_in)
        per_tick = rasters.sum(axis=2)
        self.cnt = np.zeros((pool, T + 1), np.int64)
        self.cnt[:, 1:] = np.cumsum(per_tick, axis=1)
        words = [ev.spike_words(r) for r in rasters]
        self.start = np.concatenate([[0], np.cumsum([len(w) for w in words])])
        base = np.concatenate(words)
        # One table per character position: the characters' words with their
        # ticks moved to that position in the stream.  A feed is a view.
        self.tables = [base + np.uint32(k * T) for k in range(chars)]
        self.seq = rng.integers(0, pool, (n, chars))
        self.label = labels[self.seq[:, 0]]

        lo, hi = feed_ticks
        m = -(-T // lo)
        u = rng.random((n, chars, m))
        size = np.maximum(lo, np.rint(np.exp(
            math.log(lo) + u * (math.log(hi) - math.log(lo))))).astype(np.int64)
        end = np.cumsum(size, axis=2)
        begin = end - size
        keep = begin < T
        s_idx, k_idx, _ = np.nonzero(keep)
        a, b = begin[keep], np.minimum(end, T)[keep]
        c = self.seq[s_idx, k_idx]
        self.f_k = k_idx
        self.f_lo = self.start[c] + self.cnt[c, a]
        self.f_hi = self.start[c] + self.cnt[c, b]
        self.f_end = k_idx * T + b                 # stream tick after the feed
        counts = np.bincount(s_idx, minlength=n)
        self.feed_off = np.concatenate([[0], np.cumsum(counts)])
        self.next = self.feed_off[:-1].copy()      # each session's next feed
        ev_char = self.cnt[self.seq, T]                      # (n, chars)
        self.cum = np.zeros((n, chars + 1), np.int64)
        self.cum[:, 1:] = np.cumsum(ev_char, axis=1)

    def words(self, f: int) -> np.ndarray:
        return self.tables[self.f_k[f]][self.f_lo[f]:self.f_hi[f]]

    def last_tick(self, f: int, prev: int) -> int:
        """The session's newest fed tick after feed ``f``."""
        if self.f_hi[f] == self.f_lo[f]:
            return prev
        return int(self.tables[self.f_k[f]][self.f_hi[f] - 1] & ev.MAX_TICK)

    def label_word(self, s: int) -> np.ndarray:
        return np.array([ev.word(ev.EVT_LABEL, self.label[s], 0)], np.uint32)

    def events_before(self, ticks: np.ndarray) -> np.ndarray:
        """Spike events of each session's stream before ``ticks[s]``."""
        s = np.arange(self.n)
        k = np.minimum(ticks // self.T, self.K)
        t = np.where(k < self.K, ticks % self.T, 0)
        c = self.seq[s, np.minimum(k, self.K - 1)]
        part = np.where(k < self.K, self.cnt[c, t], 0)
        return self.cum[s, k] + part

    def raster(self, sessions: np.ndarray, T_max: int) -> np.ndarray:
        chars = -(-T_max // self.T)
        r = self.rasters[self.seq[sessions, :chars]]        # (b, chars, T, n_in)
        r = r.reshape(len(sessions), chars * self.T, -1)[:, :T_max]
        return np.ascontiguousarray(r.transpose(1, 0, 2))


def schedule(seed: int, streams: Streams, tr: dict, seconds: float):
    """Open-loop arrivals ``(due_s, session, feed)`` sorted by due time, and
    the bursts dropped because a stream ran out.

    Bursts start at uniform (Poisson-conditioned) times on sessions drawn
    from a Zipf law; each slides over a geometric number of characters at
    ``session_ticks_per_s``, a feed falling due when its last tick has been
    sensed.  A session slides one burst at a time, so its hot sessions are
    always on and the rest share what remains: the number of bursts is
    scaled until the drawn feeds offer ``offered_events_per_s`` within 1%.
    """
    n, T = streams.n, streams.T
    target = tr["offered_events_per_s"]
    per_tick = streams.cnt[:, T].mean() / T
    mean_chars = tr["burst_characters_mean"]
    n_bursts = int(round(target * seconds / (mean_chars * T * per_tick)))
    for _ in range(8):
        out = _bursts(np.random.default_rng(seed), streams, tr, seconds,
                      n_bursts)
        offered = (streams.f_hi[out[2]] - streams.f_lo[out[2]]).sum() / seconds
        if abs(offered / target - 1.0) < 0.01:
            break
        n_bursts = int(round(n_bursts * target / max(offered, 1.0)))
    return out


def _bursts(rng, streams: Streams, tr: dict, seconds: float, n_bursts: int):
    n, T = streams.n, streams.T
    starts = np.sort(rng.uniform(0.0, seconds, n_bursts))
    ranks = np.arange(1, n + 1, dtype=np.float64) ** -tr["zipf_s"]
    who = rng.permutation(n)[rng.choice(n, n_bursts, p=ranks / ranks.sum())]
    n_chars = rng.geometric(1.0 / tr["burst_characters_mean"], n_bursts)
    tick_s = 1.0 / tr["session_ticks_per_s"]
    free = np.zeros(n)
    due, sess, feed = [], [], []
    ptr = streams.next.copy()
    dropped = 0
    for t0, s, c in zip(starts, who, n_chars):
        t0 = max(t0, free[s])
        if t0 >= seconds:
            continue
        f = ptr[s]
        first = streams.f_end[f - 1] if f > streams.feed_off[s] else 0
        stop_tick = (first // T + c) * T
        while f < streams.feed_off[s + 1] and streams.f_end[f] <= stop_tick:
            d = t0 + (streams.f_end[f] - first) * tick_s
            if d < seconds:
                due.append(d)
                sess.append(s)
                feed.append(f)
            f += 1
        if f >= streams.feed_off[s + 1]:
            dropped += 1
        ptr[s] = f
        free[s] = t0 + (stop_tick - first) * tick_s
    order = np.argsort(due, kind="stable")
    return (np.asarray(due)[order], np.asarray(sess, np.int64)[order],
            np.asarray(feed, np.int64)[order], dropped)


def _build(run: Run):
    import jax.numpy as jnp
    from repro.serve import BatchedEngine

    from bench.model import make_weights, rsnn_config

    c, tr = run.config, run.traffic
    run.mark("importing the program")
    rng = np.random.default_rng(subseed(run.seed, 1))
    streams = Streams(rng, tr["sessions"], tr["characters_per_session"],
                      tr["character_pool"], tr["letters"], c["sample_ticks"],
                      tr["feed_ticks"])
    run.mark("drawing the streams")
    weights = make_weights(c, subseed(run.seed, 2))
    cfg = rsnn_config(c, c["sample_ticks"])
    engine = BatchedEngine(cfg, dict(weights, alpha=jnp.float32(cfg.neuron.alpha)),
                           tick_tile=tr["tick_tile"])
    jnp.zeros(()).block_until_ready()
    run.mark("weights and engine")
    return streams, weights, engine


def _feed(handles, streams, s, last) -> bool:
    """Feed session ``s`` its next feed; False once its stream has run
    out."""
    f = streams.next[s]
    if f >= streams.feed_off[s + 1]:
        return False
    handles[s].feed(streams.words(f))
    streams.next[s] = f + 1
    last[s] = streams.last_tick(f, last[s])
    return True


def _seat_and_warm(run, engine, handles, streams, last):
    """Seat every session in the pool, then launch one tile of each padded
    width the window can produce, so nothing compiles inside it."""
    for s, h in enumerate(handles):
        h.feed(streams.label_word(s))
        _feed(handles, streams, s, last)
    engine.pump(drain=True)
    run.mark("seating the sessions")
    width = 1
    s = 0
    while width <= engine.max_batch:
        for _ in range(width):
            _feed(handles, streams, s % streams.n, last)
            s += 1
        engine.pump(drain=True)
        width *= 2
    run.mark("warming the tile widths")


def _harvested_ticks(handles) -> np.ndarray:
    snaps = [h.poll() for h in handles]
    return np.array([0 if p is None else p.ticks for p in snaps], np.int64)


def run(run: Run) -> None:
    tr = run.traffic
    streams, weights, engine = _build(run)
    handles = [engine.open_session() for _ in range(streams.n)]
    last = np.zeros(streams.n, np.int64)
    run.mark("opening the sessions")
    _seat_and_warm(run, engine, handles, streams, last)
    if tr["loop"] == "open":
        due, sess, feed, dropped = schedule(subseed(run.seed, 3), streams,
                                            tr, run.seconds)
        run.stats["bursts_dropped"] = dropped
        run.stats["offered_events_per_s"] = float(
            (streams.f_hi[feed] - streams.f_lo[feed]).sum()) / run.seconds
        run.mark("drawing the schedule")
    start = _harvested_ticks(handles)
    engine.reset_stream_stats()
    with run.window():
        t0 = time.perf_counter()
        if tr["loop"] == "closed":
            attempted, failed, feed_s, pump_s = _closed(
                run, engine, handles, streams, last, t0)
        else:
            attempted, failed, feed_s, pump_s, lat, lag = _open(
                run, engine, handles, streams, last, t0, due, sess, feed)
        with run.span("drain"):
            engine.pump(drain=True)
    stats = engine.stream_stats(run.window_s)
    end = _harvested_ticks(handles)
    served = streams.events_before(end) - streams.events_before(start)
    run.attempted, run.failed = attempted, failed
    run.stats.update(
        feed_s=feed_s, feed_calls=attempted, pump_s=pump_s,
        tiles=stats.tiles, lanes=stats.mean_lanes * stats.tiles,
        events=stats.events, launched_ticks=stats.ticks,
        session_ticks=int((end - start).sum()),
        served_events=int(served.sum()), quarantined=stats.quarantined,
        exhausted=int((streams.next >= streams.feed_off[1:]).sum()))
    run.e2e["served_events_per_s"] = served.sum() / run.window_s
    if tr["loop"] == "open":
        run.e2e["result_latency_p95_ms"] = 1e3 * float(np.percentile(lat, 95))
        run.stats["gen_lag_p95_ms"] = 1e3 * float(np.percentile(lag, 95))
    run.read_memory()
    from bench.reference import Datapath

    compare(run, Datapath.from_config(run.config), weights, streams, end,
            _program(engine, handles))
    run.evidence = {"streams": streams, "weights": weights, "ticks": end}
    if tr["loop"] == "open":
        run.evidence.update(latency_s=lat, due_s=due)


def _closed(run, engine, handles, streams, last, t0):
    from repro.serve import GuardError

    block = run.traffic["feed_block"]
    n = streams.n
    attempted = failed = 0
    feed_s = pump_s = 0.0
    nxt = 0
    while time.perf_counter() - t0 < run.seconds:
        a = time.perf_counter()
        with run.span("feed"):
            for s in range(nxt, nxt + block):
                s %= n
                if streams.next[s] >= streams.feed_off[s + 1]:
                    continue
                attempted += 1
                try:
                    _feed(handles, streams, s, last)
                except GuardError:
                    failed += 1
        b = time.perf_counter()
        with run.span("pump"):
            engine.pump()
        feed_s += b - a
        pump_s += time.perf_counter() - b
        nxt = (nxt + block) % n
    return attempted, failed, feed_s, pump_s


def _open(run, engine, handles, streams, last, t0, due, sess, feed):
    from repro.serve import GuardError

    horizon = run.seconds + 60.0
    lat = np.full(len(due), horizon)
    lag = np.zeros(len(due))
    waiting: dict = {}
    attempted = failed = 0
    feed_s = pump_s = 0.0
    i = 0
    while True:
        now = time.perf_counter() - t0
        j = int(np.searchsorted(due, now, side="right"))
        a = time.perf_counter()
        with run.span("feed"):
            for q in range(i, j):
                s = int(sess[q])
                attempted += 1
                lag[q] = time.perf_counter() - t0 - due[q]
                try:
                    handles[s].feed(streams.words(feed[q]))
                except GuardError:
                    failed += 1
                    continue
                last[s] = streams.last_tick(feed[q], last[s])
                waiting.setdefault(s, []).append((last[s], q))
        i = j
        b = time.perf_counter()
        with run.span("pump"):
            engine.pump()
        c = time.perf_counter()
        with run.span("poll"):
            _collect(handles, waiting, lat, due, t0)
        feed_s += b - a
        pump_s += c - b
        if i >= len(due) and now >= run.seconds:
            break
    with run.span("drain"):
        engine.pump(drain=True)
        _collect(handles, waiting, lat, due, t0)
    return attempted, failed, feed_s, pump_s, lat, lag


def _collect(handles, waiting, lat, due, t0):
    now = time.perf_counter() - t0
    for s in list(waiting):
        snap = handles[s].poll()
        ticks = snap.ticks if snap is not None else 0
        pend = waiting[s]
        while pend and ticks >= pend[0][0]:
            _, q = pend.pop(0)
            lat[q] = now - due[q]
        if not pend:
            del waiting[s]


def compare(run: Run, dp, weights, streams, ticks, got) -> None:
    """Every session's carry and last readout against the reference over
    the same stream ticks, in blocks of sessions.  ``got(idx)`` gives the
    ``(carry, readout)`` of sessions ``idx`` from the program, or from a
    stand-in put in its place."""
    from bench.reference import run_streams

    w = {k: np.asarray(v) for k, v in weights.items()}
    state_bad = snap_bad = 0
    block = 2048
    for b0 in range(0, streams.n, block):
        idx = np.arange(b0, min(b0 + block, streams.n))
        t = ticks[idx]
        ref = run_streams(dp, w, streams.raster(idx, int(t.max())), t)
        carry, readout = got(idx)
        for k, v in ref.items():
            state_bad += int((carry[k] != v).sum())
        snap_bad += int((readout != ref["acc_y"]).sum())
    run.check("state_mismatches", state_bad, 0)
    run.check("readout_mismatches", snap_bad, 0)
    run.check("failed_feeds", run.failed, 0)
    run.check("quarantined", run.stats["quarantined"], 0)


def _program(engine, handles):
    """The timed path's answers: each session's carry in the pool and its
    last harvested readout."""
    pool = {k: np.asarray(v) for k, v in engine.pool.state.items()}
    slots = np.array([h._sess.slot for h in handles])
    snaps = np.stack([h.poll().logits for h in handles])
    return lambda idx: ({k: v[slots[idx]] for k, v in pool.items()}, snaps[idx])


def stand_in(dp, weights, streams, ticks, rnd):
    """The reference carried at a lower precision, in the program's place."""
    from bench.reference import run_streams

    w = {k: np.asarray(v) for k, v in weights.items()}

    def got(idx):
        c = run_streams(dp, w, streams.raster(idx, int(ticks[idx].max())),
                        ticks[idx], rnd=rnd)
        return c, c["acc_y"]

    return got
