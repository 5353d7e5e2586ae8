"""Schedulers for the batched serving runtime: whole-sample bucketing and
continuous session batching.

The FPGA controller serves one AER sample at a time (IDLE → READM → TICK →
… → END_S).  At service scale that FSM becomes a *scheduler*; two live here:

* :class:`BucketingScheduler` — the whole-sample path: concurrent sample
  streams are admitted into a queue, grouped by padded tick length
  ("buckets"), and released as rectangular batch tiles sized to the
  kernel's VMEM budget (:func:`repro.serve.batching.max_batch_for`).
* :class:`StreamPacker` — the streaming path's continuous-batching
  generalization: open *sessions* with pending processable ticks queue FIFO,
  and each call packs whichever ≤ ``max_batch`` sessions are ready into the
  next fixed-shape tick-tile (partially drained sessions immediately
  re-queue), so device tiles stay full while every session advances
  incrementally.

Both queues are **bounded** (``max_pending``) with an explicit admission
policy — ``"reject"`` raises :class:`~repro.serve.guard.OverloadError` at
the caller, ``"shed"`` drops the *oldest* queued work to make room (fresh
work has the best chance of meeting its deadline) — and the bucketing
scheduler tracks per-request **deadlines** so expired work is dropped at
pack time, before a device launch is paid for it.

Determinism contract (tested in ``tests/test_serve.py``): admission order is
FIFO within a bucket/queue, buckets drain in ascending tick length, and the
same request sequence always yields the same tiles — no wall-clock
dependence in tile *composition* (the clock only stamps latency accounting
and deadline checks; with no deadlines set, tiles are clock-independent).
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.serve import batching
from repro.serve.guard import OverloadError

ADMISSION_POLICIES = ("reject", "shed")


def _check_admission(admission: str) -> str:
    if admission not in ADMISSION_POLICIES:
        raise ValueError(
            f"admission must be one of {ADMISSION_POLICIES}, got {admission!r}"
        )
    return admission


@dataclasses.dataclass
class ServeRequest:
    """One admitted AER sample stream."""

    rid: int                      # admission index, unique per scheduler
    events: np.ndarray            # ragged uint32 AER buffer (§3.1 word format)
    native_ticks: int             # end-of-sample tick + 1
    bucket: int                   # padded tick length this request serves at
    t_submit: float               # admission timestamp (latency accounting)
    meta: Optional[dict] = None
    deadline: Optional[float] = None  # absolute clock time; None = no deadline


@dataclasses.dataclass
class BatchTile:
    """A rectangular unit of work: ≤ max_batch requests, one tick length."""

    num_ticks: int
    requests: List[ServeRequest]

    def __len__(self) -> int:
        return len(self.requests)


class BucketingScheduler:
    """FIFO admission → per-tick-length buckets → ≤ ``max_batch`` tiles.

    ``tick_granularity`` trades padding waste against compiled-program
    diversity: every request pays at most ``granularity - 1`` dead ticks,
    and the engine compiles at most ``ceil(max_ticks / granularity)``
    distinct time lengths.

    ``rid_alloc`` injects the request-id counter.  A multi-model engine
    runs one scheduler per registered model (tiles must stay single-model —
    one network per launch, like one SRAM image per chip program) but hands
    every scheduler the same allocator, so rids stay unique and
    admission-ordered across the whole engine.

    ``max_pending`` bounds the queue (``None`` = unbounded, the legacy
    behaviour); on overflow, ``admission="reject"`` refuses the *new*
    request with :class:`OverloadError` while ``admission="shed"`` evicts
    the oldest queued request into :attr:`shed` (the engine converts shed
    rids into REJECTED results).  ``take_expired`` removes deadline-passed
    requests — the engine calls it immediately before packing tiles so an
    expired request never occupies a launch slot.
    """

    def __init__(
        self,
        max_batch: int,
        tick_granularity: int = 32,
        clock: Callable[[], float] = time.monotonic,
        rid_alloc: Optional[Callable[[], int]] = None,
        max_pending: Optional[int] = None,
        admission: str = "reject",
    ):
        if max_batch < 1 or tick_granularity < 1:
            raise ValueError(
                f"max_batch and tick_granularity must be >= 1, got "
                f"({max_batch}, {tick_granularity})"
            )
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self.max_batch = max_batch
        self.tick_granularity = tick_granularity
        self.max_pending = max_pending
        self.admission = _check_admission(admission)
        self._clock = clock
        self._buckets: Dict[int, List[ServeRequest]] = OrderedDict()
        self._next_rid = 0
        self._rid_alloc = rid_alloc or self._alloc_rid
        self.shed: List[ServeRequest] = []   # evicted under admission="shed"

    def _alloc_rid(self) -> int:
        rid = self._next_rid
        self._next_rid += 1
        return rid

    def submit(
        self,
        events: np.ndarray,
        meta: Optional[dict] = None,
        deadline: Optional[float] = None,
    ) -> int:
        """Admit one AER sample stream; returns its request id.

        ``deadline`` is an *absolute* clock time (same clock the scheduler
        was built with); a request whose deadline passes before it is
        packed is dropped by :meth:`take_expired` and reported EXPIRED.
        Raises :class:`OverloadError` when the queue is full under the
        ``"reject"`` policy.
        """
        if self.max_pending is not None and self.pending >= self.max_pending:
            if self.admission == "reject":
                raise OverloadError(
                    f"scheduler queue full ({self.pending} pending, "
                    f"max_pending={self.max_pending}); retry later or use "
                    'admission="shed"'
                )
            self.shed.append(self._pop_oldest())
        events = batching.trim_padding(events)
        native = batching.request_ticks(events)
        bucket = batching.bucket_ticks(native, self.tick_granularity)
        req = ServeRequest(
            rid=self._rid_alloc(),
            events=events,
            native_ticks=native,
            bucket=bucket,
            t_submit=self._clock(),
            meta=meta,
            deadline=deadline,
        )
        self._buckets.setdefault(bucket, []).append(req)
        return req.rid

    def _pop_oldest(self) -> ServeRequest:
        """Remove and return the queued request with the lowest rid (the
        oldest admission) — the shed victim."""
        best_key, best_i = None, -1
        for ticks, queue in self._buckets.items():
            # FIFO within a bucket: index 0 is that bucket's oldest.
            if queue and (best_key is None
                          or queue[0].rid < self._buckets[best_key][0].rid):
                best_key = ticks
        queue = self._buckets[best_key]
        victim = queue.pop(0)
        if not queue:
            del self._buckets[best_key]
        return victim

    def take_expired(self, now: Optional[float] = None) -> List[ServeRequest]:
        """Remove and return every queued request whose deadline has
        passed.  Called at pack time so expired work never launches."""
        now = self._clock() if now is None else now
        expired: List[ServeRequest] = []
        for ticks in list(self._buckets):
            queue = self._buckets[ticks]
            keep = []
            for req in queue:
                if req.deadline is not None and now > req.deadline:
                    expired.append(req)
                else:
                    keep.append(req)
            if keep:
                self._buckets[ticks] = keep
            else:
                del self._buckets[ticks]
        return expired

    @property
    def pending(self) -> int:
        return sum(len(v) for v in self._buckets.values())

    def ready_tiles(self) -> Iterator[BatchTile]:
        """Release only *full* tiles (steady-state serving keeps partial
        buckets queued for more arrivals)."""
        yield from self._drain(full_only=True)

    def drain(self) -> Iterator[BatchTile]:
        """Release everything, full tiles first within each bucket —
        end-of-stream flush."""
        yield from self._drain(full_only=False)

    def _drain(self, full_only: bool) -> Iterator[BatchTile]:
        for ticks in sorted(self._buckets):
            queue = self._buckets[ticks]
            tiles = batching.split_into_tiles(queue, self.max_batch)
            keep: List[ServeRequest] = []
            for tile in tiles:
                if full_only and len(tile) < self.max_batch:
                    keep.extend(tile)
                else:
                    yield BatchTile(num_ticks=ticks, requests=tile)
            self._buckets[ticks] = keep
        self._buckets = OrderedDict(
            (k, v) for k, v in self._buckets.items() if v
        )


class StreamPacker:
    """Continuous batching over open sessions.

    Sessions enter the FIFO ready-queue when they gain processable ticks
    (:meth:`enqueue`); :meth:`next_tile` pops up to ``max_batch`` of them
    and picks the tile's tick length: the fixed ``tick_tile`` when one is
    configured (latency-bounded true streaming), otherwise the bucketed
    maximum of the chosen sessions' pending ticks (throughput mode — one
    launch drains everything pending, which is what the whole-sample
    compatibility wrapper uses so its per-launch work matches the old
    bucketing path).  A session whose chunk didn't drain it is re-queued by
    the engine after the tile is cut, preserving FIFO fairness.

    ``max_pending`` bounds the ready-queue *length* (sessions, not events;
    per-session event memory is bounded separately by the guard's
    ``max_pending_events`` quota).  The packer has no shed policy of its
    own — a session is stateful, so "shedding" it is the engine's call
    (the engine pumps inline instead, accounting the stall as admission
    wait); :meth:`enqueue` just reports the overflow via its return value.

    While :mod:`repro.obs` is enabled, :meth:`enqueue` stamps a session's
    ``t_queued`` with ``clock`` as it enters the queue (``None`` otherwise),
    from which the engine records the session's wait when it is packed.
    """

    def __init__(
        self,
        max_batch: int,
        tick_tile: Optional[int] = None,
        tick_granularity: int = 32,
        max_pending: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if tick_tile is not None and tick_tile < 1:
            raise ValueError(f"tick_tile must be >= 1, got {tick_tile}")
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self.max_batch = max_batch
        self.tick_tile = tick_tile
        self.tick_granularity = tick_granularity
        self.max_pending = max_pending
        self._clock = clock
        self._queue: deque = deque()

    @property
    def full(self) -> bool:
        return (self.max_pending is not None
                and len(self._queue) >= self.max_pending)

    def enqueue(self, sess) -> bool:
        """Add a session with pending work (idempotent per residence in the
        queue — sessions track their own ``queued`` flag).  Returns False
        when the bounded queue is full and the session was *not* added; the
        engine then drains a tile inline before retrying."""
        if sess.queued:
            return True
        if self.full:
            return False
        sess.queued = True
        sess.t_queued = self._clock() if obs.enabled() else None
        self._queue.append(sess)
        return True

    @property
    def pending(self) -> int:
        return len(self._queue)

    def next_tile(self) -> Optional[Tuple[List, int]]:
        """Pop the next ``(sessions, num_ticks)`` tile, or ``None`` when no
        queued session has processable ticks."""
        chosen: List = []
        while self._queue and len(chosen) < self.max_batch:
            sess = self._queue.popleft()
            sess.queued = False
            if sess.processable() > 0:
                chosen.append(sess)
        if not chosen:
            return None
        if self.tick_tile is not None:
            ticks = self.tick_tile
        else:
            ticks = batching.bucket_ticks(
                max(s.processable() for s in chosen), self.tick_granularity
            )
        return chosen, ticks
