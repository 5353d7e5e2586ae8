"""Hardened serving (ISSUE 10): the engine-level robustness contract.

Input guards reject malformed AER traffic with typed errors while
neighbours serve unaffected; bounded admission queues reject or shed under
overload; deadlines drop work at pack time (before any launch); numeric
health checks quarantine one poisoned session while its tile-mates deliver
bitwise-unchanged; and a faulted lane restarts — rebuilt backend, sessions
re-seated from bit-exact eviction snapshots — with final results bitwise
equal to an undisturbed run, also under mid-stream fuzz and repeated
launch faults on the scan, quantized and kernel datapaths.
"""

import jax
import numpy as np
import pytest

from repro.core import aer, eprop
from repro.core.backend import CompileError
from repro.core.rsnn import Presets, init_params
from repro.serve import (
    BatchedEngine,
    GuardConfig,
    MalformedEventError,
    OverloadError,
    QuotaExceededError,
    ServeStatus,
    StreamContractError,
)


def _request(rng, n_in, ticks, label=1):
    raster = (rng.random((ticks, n_in)) < 0.25).astype(np.float32)
    ev = aer.encode_sample(
        raster, label, label_tick=max(0, ticks // 4), end_tick=ticks - 1
    )
    ev = np.asarray(ev, np.uint32)
    return ev[np.argsort(ev & aer.MAX_TICK, kind="stable")]


def _setup(seed=0, n=6, T=48, quantized=False):
    cfg = Presets.braille(n_classes=3, num_ticks=T, quantized=quantized)
    params = init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(seed)
    reqs = [
        _request(rng, cfg.n_in, int(rng.integers(12, T + 1)), label=i % 3)
        for i in range(n)
    ]
    return cfg, params, reqs


class Clock:
    """Scripted monotonic clock for deadline tests."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


# --------------------------------------------------------------------------
# input guards at the engine boundary
# --------------------------------------------------------------------------


def test_submit_rejects_malformed_and_keeps_serving():
    cfg, params, reqs = _setup(n=3)
    eng = BatchedEngine(cfg, params, backend="scan", max_batch=4)
    with pytest.raises(MalformedEventError):
        eng.submit(np.array([0x7F000000], np.uint32))   # unknown type byte
    with pytest.raises(MalformedEventError):
        eng.submit(np.array([1.5, 2.5]))                # float dtype
    with pytest.raises(MalformedEventError):
        # spike addressed beyond the model's n_in
        bad = aer.pack(aer.EVT_SPIKE, cfg.n_in, 0)
        eng.submit(np.array([bad], np.uint32))
    # nothing was admitted; a clean request still serves
    assert eng.scheduler.pending == 0
    res, stats = eng.serve(iter(reqs))
    assert all(r.status is ServeStatus.OK for r in res)
    assert stats.rejected == 0


def test_serve_turns_bad_items_into_rejected_results():
    cfg, params, reqs = _setup(n=4)
    clean, _ = BatchedEngine(
        cfg, params, backend="scan", max_batch=4
    ).serve(iter(reqs))

    poisoned = [reqs[0], np.array([0xFF123456], np.uint32), *reqs[1:]]
    eng = BatchedEngine(cfg, params, backend="scan", max_batch=4)
    res, stats = eng.serve(iter(poisoned))
    assert len(res) == len(poisoned)
    bad = [r for r in res if r.status is ServeStatus.REJECTED]
    ok = [r for r in res if r.status is ServeStatus.OK]
    assert len(bad) == 1 and bad[0].pred == -1
    assert stats.rejected == 1 and stats.requests == len(poisoned)
    # neighbours are bitwise identical to the clean run
    for got, want in zip(ok, clean):
        assert got.pred == want.pred
        np.testing.assert_array_equal(got.logits, want.logits)


def test_guard_false_disables_validation():
    cfg, params, _ = _setup(n=1)
    eng = BatchedEngine(cfg, params, backend="scan", guard=False)
    # garbage admits without raising (legacy behaviour, at the caller's risk)
    eng.submit(np.array([0x03000000 | (999 << 12)], np.uint32))


def test_feed_guard_contract_and_quota():
    cfg, params, reqs = _setup(n=1)
    eng = BatchedEngine(
        cfg, params, backend="scan", tick_tile=8,
        guard=GuardConfig(max_pending_events=200),
    )
    h = eng.open_session()
    h.feed(reqs[0][: len(reqs[0]) // 2])
    before = eng._sessions[h.sid].n_events
    with pytest.raises(StreamContractError):
        h.feed(np.array([aer.pack(aer.EVT_SPIKE, 0, 0)], np.uint32))
    # a rejected feed leaves the session untouched and still OK
    assert eng._sessions[h.sid].n_events == before
    assert h.status is ServeStatus.OK
    t = eng._sessions[h.sid].max_fed_tick
    flood = np.array(
        [aer.pack(aer.EVT_SPIKE, 0, min(t + 1, aer.MAX_TICK))] * 201,
        np.uint32,
    )
    with pytest.raises(QuotaExceededError):
        h.feed(flood)
    with pytest.raises(StreamContractError):
        h.close()
        h.feed(reqs[0])


# --------------------------------------------------------------------------
# overload control + deadlines
# --------------------------------------------------------------------------


def test_bounded_queue_rejects_new_work():
    cfg, params, reqs = _setup(n=6)
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=4, max_pending=2
    )
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    with pytest.raises(OverloadError):
        eng.submit(reqs[2])
    # the queue never grew past its bound
    assert eng.scheduler.pending == 2


def test_shed_policy_drops_oldest_as_rejected_result():
    cfg, params, reqs = _setup(n=6)
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=4,
        max_pending=2, admission="shed",
    )
    rid0 = eng.submit(reqs[0])
    eng.submit(reqs[1])
    eng.submit(reqs[2])   # sheds rid0, admits
    dead = eng.take_dead_results()
    assert [r.rid for r in dead] == [rid0]
    assert dead[0].status is ServeStatus.REJECTED
    assert eng.scheduler.pending == 2


def test_serve_under_shed_storm_stays_bounded_and_typed():
    cfg, params, _ = _setup(n=0)
    rng = np.random.default_rng(3)
    # Distinct tick lengths land in distinct buckets, so tiles never fill
    # mid-stream and the bounded queue must shed to keep admitting.
    reqs = [
        _request(rng, cfg.n_in, 8 * (i % 5 + 1), label=i % 3)
        for i in range(12)
    ]
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=4, tick_granularity=8,
        max_pending=2, admission="shed", max_inflight_tiles=1,
    )
    res, stats = eng.serve(iter(reqs))
    assert len(res) == len(reqs)
    assert stats.requests == len(reqs)
    by = {s: sum(1 for r in res if r.status is s) for s in ServeStatus}
    assert by[ServeStatus.OK] + by[ServeStatus.REJECTED] == len(reqs)
    assert stats.shed == by[ServeStatus.REJECTED] > 0


def test_deadline_expires_before_launch():
    cfg, params, reqs = _setup(n=3)
    clk = Clock()
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=4, clock=clk,
        default_deadline_s=5.0,
    )
    rid = eng.submit(reqs[0])
    clk.now = 10.0   # past the deadline before anything packs
    eng.submit(reqs[1], deadline_s=100.0)
    dead = eng.take_dead_results()
    assert [r.rid for r in dead] == [rid]
    assert dead[0].status is ServeStatus.EXPIRED
    # the survivor still serves through the normal drain
    tiles = eng.scheduler.drain()
    assert sum(len(t.requests) for t in tiles) == 1


def test_session_deadline_drops_at_pack_time():
    cfg, params, reqs = _setup(n=2)
    clk = Clock()
    eng = BatchedEngine(
        cfg, params, backend="scan", tick_tile=8, clock=clk,
    )
    doomed = eng.open_session(deadline_s=5.0)
    healthy = eng.open_session()
    doomed.feed(reqs[0])
    healthy.feed(reqs[1])
    clk.now = 10.0
    eng.pump(drain=True)
    assert doomed.status is ServeStatus.EXPIRED
    snap = doomed.result()
    assert snap.final and snap.status is ServeStatus.EXPIRED and snap.pred == -1
    ok = healthy.result()
    assert ok.status is ServeStatus.OK and ok.pred >= 0
    stats = eng.stream_stats(wall_s=1.0)
    assert stats.expired == 1


# --------------------------------------------------------------------------
# fault-isolated tiles + lane supervision
# --------------------------------------------------------------------------


def _flaky_hook(fail_on, kinds=("tile", "stream")):
    """A fault_hook raising on scripted launch indices (engine-wide)."""
    count = [0]

    def hook(model_id, kind):
        if kind not in kinds:
            return
        count[0] += 1
        if count[0] in fail_on:
            raise RuntimeError(f"injected launch fault #{count[0]}")

    return hook


def test_whole_sample_launch_fault_recovers_bitwise():
    cfg, params, reqs = _setup(n=6)
    clean, _ = BatchedEngine(
        cfg, params, backend="scan", max_batch=4
    ).serve(iter(reqs))

    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=4,
        fault_hook=_flaky_hook({1}),
    )
    res, stats = eng.serve(iter(reqs))
    assert stats.lane_restarts == 1
    assert all(r.status is ServeStatus.OK for r in res)
    for got, want in zip(res, clean):
        assert got.pred == want.pred
        np.testing.assert_array_equal(got.logits, want.logits)


def test_whole_sample_fault_budget_exhaustion_faults_tile():
    cfg, params, reqs = _setup(n=2)
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=4, max_tile_retries=1,
        fault_hook=_flaky_hook(set(range(1, 100))),   # every launch fails
    )
    res, stats = eng.serve(iter(reqs))
    assert len(res) == len(reqs)
    assert all(r.status is ServeStatus.FAULT for r in res)
    assert all(r.pred == -1 for r in res)
    assert stats.quarantined == len(reqs)
    # the engine survives and serves cleanly once the faults stop
    eng._fault_hook = None
    res2, _ = eng.serve(iter(reqs))
    assert all(r.status is ServeStatus.OK for r in res2)


def test_stream_launch_fault_rewinds_and_recovers_bitwise():
    cfg, params, reqs = _setup(n=4, T=32)

    def run(hook):
        eng = BatchedEngine(
            cfg, params, backend="scan", max_batch=4, tick_tile=8,
            fault_hook=hook,
        )
        handles = [eng.open_session() for _ in reqs]
        for h, ev in zip(handles, reqs):
            mid = len(ev) // 2
            h.feed(ev[:mid])
            h.feed(ev[mid:])
        eng.pump(drain=True)
        snaps = [h.result() for h in handles]
        return eng, snaps

    _, clean = run(None)
    eng, got = run(_flaky_hook({2}, kinds=("stream",)))
    assert eng.stream_stats(1.0).lane_restarts == 1
    for g, w in zip(got, clean):
        assert g.status is ServeStatus.OK
        assert (g.pred, g.ticks, g.events) == (w.pred, w.ticks, w.events)
        np.testing.assert_array_equal(g.logits, w.logits)


def test_stream_fault_budget_quarantines_sessions():
    cfg, params, reqs = _setup(n=2, T=32)
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=4, tick_tile=8,
        max_tile_retries=0,
        fault_hook=_flaky_hook(set(range(1, 100)), kinds=("stream",)),
    )
    h = eng.open_session()
    h.feed(reqs[0])
    eng.pump(drain=True)
    assert h.status is ServeStatus.FAULT
    snap = h.result()
    assert snap.final and snap.status is ServeStatus.FAULT and snap.pred == -1
    stats = eng.stream_stats(1.0)
    assert stats.quarantined == 1 and stats.lane_restarts >= 1
    # fresh sessions on the rebuilt lane serve normally
    eng._fault_hook = None
    h2 = eng.open_session()
    h2.feed(reqs[1])
    assert h2.result().status is ServeStatus.OK


def _stream_halves(eng, reqs, abuse=None):
    """Feed every session its events in two halves, one pump per round,
    with ``abuse(eng, handles)`` between the feeds and the pump; the final
    snapshots in session order."""
    handles = [eng.open_session() for _ in reqs]
    for step in range(2):
        for h, ev in zip(handles, reqs):
            mid = len(ev) // 2
            h.feed(ev[:mid] if step == 0 else ev[mid:])
        if abuse is not None:
            abuse(eng, handles)
        eng.pump()
    eng.pump(drain=True)
    return [h.result() for h in handles]


@pytest.mark.parametrize(
    "backend,quantized",
    [("scan", False), ("scan", True), ("kernel", False)],
    ids=["scan-float", "scan-quant", "kernel"],
)
def test_fuzz_and_launch_faults_mid_stream_leave_sessions_bitwise(
    backend, quantized
):
    """Mid-stream, raw 32-bit noise is fed to one live session and submitted
    as a whole sample, and every third streaming launch fails.  Every noise
    buffer is refused with a typed error, the lane restarts, and every
    session ends bitwise equal to an undisturbed run."""
    cfg, params, reqs = _setup(seed=0, n=6, T=48, quantized=quantized)
    rng = np.random.default_rng(0)
    kw = dict(backend=backend, max_batch=4, tick_tile=8)
    clean = _stream_halves(BatchedEngine(cfg, params, **kw), reqs)

    refused = []

    def fuzz(eng, handles):
        for _ in range(8):
            noise = rng.integers(0, 2**32, int(rng.integers(1, 32)), np.uint32)
            with pytest.raises(aer.AEREncodingError):
                handles[0].feed(noise)
            refused.append(noise)
        with pytest.raises(aer.AEREncodingError):
            eng.submit(rng.integers(0, 2**32, 16, np.uint32))

    fuzzed = _stream_halves(BatchedEngine(cfg, params, **kw), reqs, fuzz)
    assert len(refused) == 16

    eng = BatchedEngine(
        cfg, params, fault_hook=_flaky_hook(set(range(3, 100, 3)),
                                            kinds=("stream",)), **kw
    )
    faulted = _stream_halves(eng, reqs)
    assert eng.stream_stats(1.0).lane_restarts >= 1

    for got in (fuzzed, faulted):
        for g, w in zip(got, clean):
            assert g.status is w.status is ServeStatus.OK
            assert g.pred == w.pred
            np.testing.assert_array_equal(g.logits, w.logits)


@pytest.mark.parametrize("path", ["serve", "pump"])
def test_program_build_error_raises_instead_of_faulting(monkeypatch, path):
    """A launch whose program fails to lower is a deterministic program
    error, not a device fault: ``serve``/``pump`` raise
    :class:`CompileError` on the first launch instead of restarting the lane
    and returning FAULT results."""
    cfg, params, reqs = _setup(n=2, T=32)
    eng = BatchedEngine(cfg, params, backend="scan", max_batch=4, tick_tile=8)

    def refuse(*args, **kwargs):
        raise NotImplementedError("lowering refused")

    monkeypatch.setattr(eprop, "run_stream_inference", refuse)
    with pytest.raises(CompileError, match="lowering refused"):
        if path == "serve":
            eng.serve(iter(reqs))
        else:
            eng.open_session().feed(reqs[0])
            eng.pump(drain=True)
    stats = eng.stream_stats(1.0)
    assert stats.lane_restarts == 0 and stats.quarantined == 0


def test_harvest_nan_quarantines_one_session_tile_mates_unchanged():
    cfg, params, reqs = _setup(n=3, T=32)

    def run(victim_idx):
        eng = BatchedEngine(
            cfg, params, backend="scan", max_batch=4, tick_tile=8,
        )
        handles = [eng.open_session() for _ in reqs]
        if victim_idx is not None:
            victim_sid = handles[victim_idx].sid
            orig = eng._launch_chunks

            def poisoned(lane, sessions, chunks, num_ticks):
                out = orig(lane, sessions, chunks, num_ticks)
                for i, s in enumerate(sessions):
                    if s.sid == victim_sid:
                        out = dict(out)
                        out["acc_y"] = out["acc_y"].at[i].set(float("nan"))
                return out

            eng._launch_chunks = poisoned
        for h, ev in zip(handles, reqs):
            h.feed(ev)
        eng.pump(drain=True)
        return eng, handles

    _, clean = run(None)
    clean_snaps = [h.result() for h in clean]
    eng, handles = run(victim_idx=1)
    assert handles[1].status is ServeStatus.FAULT
    snap = handles[1].result()
    assert snap.status is ServeStatus.FAULT and snap.pred == -1
    assert not snap.logits.any()
    # tile-mates delivered bitwise-identical to the undisturbed run
    for i in (0, 2):
        s = handles[i].result()
        assert s.status is ServeStatus.OK
        np.testing.assert_array_equal(s.logits, clean_snaps[i].logits)
    assert eng.stream_stats(1.0).quarantined == 1


def test_quantized_saturation_storm_quarantines():
    cfg, params, reqs = _setup(n=2, T=32, quantized=True)
    eng = BatchedEngine(cfg, params, backend="scan", tick_tile=8)
    handles = [eng.open_session() for _ in reqs]
    sid = handles[0].sid
    orig = eng._launch_chunks

    def stormy(lane, sessions, chunks, num_ticks):
        out = orig(lane, sessions, chunks, num_ticks)
        for i, s in enumerate(sessions):
            if s.sid == sid:
                out = dict(out)
                out["acc_y"] = out["acc_y"].at[i].set(1e12)   # off-grid
        return out

    eng._launch_chunks = stormy
    for h, ev in zip(handles, reqs):
        h.feed(ev)
    eng.pump(drain=True)
    assert handles[0].status is ServeStatus.FAULT
    assert handles[1].status is ServeStatus.OK
    stats = eng.stream_stats(1.0)
    assert stats.saturation_storms >= 1 and stats.quarantined == 1


# --------------------------------------------------------------------------
# backpressure accounting + stats plumbing
# --------------------------------------------------------------------------


def test_bounded_packer_pumps_inline_and_accounts_wait():
    cfg, params, reqs = _setup(n=4, T=32)
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=2, tick_tile=8,
        max_pending_sessions=1,
    )
    eng.reset_stream_stats()
    handles = [eng.open_session() for _ in reqs]
    for h, ev in zip(handles, reqs):
        h.feed(ev)   # overflows the 1-deep ready queue; engine pumps inline
    eng.pump(drain=True)
    snaps = [h.result() for h in handles]
    assert all(s.status is ServeStatus.OK for s in snaps)
    stats = eng.stream_stats(wall_s=1.0)
    assert stats.admission_wait_s >= 0.0
    assert stats.events_per_sec > 0

    # bitwise-equal to an unbounded engine: backpressure only reorders
    eng2 = BatchedEngine(cfg, params, backend="scan", max_batch=2, tick_tile=8)
    h2 = [eng2.open_session() for _ in reqs]
    for h, ev in zip(h2, reqs):
        h.feed(ev)
    for s, t in zip(snaps, (h.result() for h in h2)):
        np.testing.assert_array_equal(s.logits, t.logits)


def test_stats_carry_error_counters():
    cfg, params, reqs = _setup(n=3)
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=4,
        fault_hook=_flaky_hook({1}),
    )
    bad = np.array([0xAA000000], np.uint32)
    res, stats = eng.serve(iter([*reqs, bad]))
    assert stats.requests == len(reqs) + 1
    assert stats.rejected == 1
    assert stats.lane_restarts == 1
    # serve()'s throughput/latency cover only the OK results
    ok = [r for r in res if r.status is ServeStatus.OK]
    assert stats.samples_per_sec >= 0 and len(ok) == len(reqs)


def test_dead_results_drain_once():
    cfg, params, reqs = _setup(n=3)
    eng = BatchedEngine(
        cfg, params, backend="scan", max_pending=1, admission="shed"
    )
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    assert len(eng.take_dead_results()) == 1
    assert eng.take_dead_results() == []
