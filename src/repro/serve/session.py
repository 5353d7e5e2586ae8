"""Device-resident session state for streaming serving.

The paper's headline edge scenario is an unbounded per-user AER event stream
classified *online* — persistent recurrent state, events arriving in
arbitrarily small increments.  This module is the state half of that
runtime: a :class:`SessionPool` owns ``(S_cap + 1, ·)`` device arrays
holding every resident session's carry ``(v, z, y, acc_y, n_spk)`` (row
``S_cap`` is the trash slot padded tile lanes read/write so gather/scatter
shapes stay fixed), with LRU + idle-timeout admission control that offloads
cold sessions to host memory bit-exactly — in quantized mode the carries
are integers on the 12-bit membrane grid, so evict → readmit → continue is
indistinguishable from an uninterrupted stream.

The *capacity unit* of streaming serving is the pool, not the batch:
one session costs :func:`repro.kernels.rsnn_step.session_state_bytes`
(``4·(2H + 2O + 1)`` bytes) regardless of how long it lives, and
:func:`repro.serve.batching.max_sessions_for` turns a byte budget into
``S_cap``.  Tiles stay sized by ``vmem_budget`` exactly as before — the two
budgets are independent (HBM-resident pool vs VMEM-resident tile).

Multi-model serving runs **one pool per registered model**: carry shapes
are ``(·, n_hid)`` / ``(·, n_out)``, which differ per network, so a
session is pinned to its model's pool (``_Session.model_id``) for life and
eviction/readmission policy is per-model — capacity math adds up over the
models an engine serves (see ``docs/serving.md``).

Host-side bookkeeping lives in :class:`_Session` (pending spike events,
stream cursor, label/END scalars); the public face is
:class:`repro.serve.engine.SessionHandle` (``feed`` / ``poll`` / ``result``
/ ``close``), handed out by ``BatchedEngine.open_session()``.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.aer import EVT_END, EVT_LABEL, EVT_SPIKE, MAX_ADDR, MAX_TICK
from repro.serve.guard import ServeStatus, StreamContractError

STATE_KEYS = ("v", "z", "y", "acc_y", "n_spk")


@dataclasses.dataclass
class SessionSnapshot:
    """One incremental (or final) per-session readout observation."""

    sid: int
    pred: int                 # argmax over the accumulated readout so far
    logits: np.ndarray        # acc_y snapshot, shape (n_out,)
    label: int                # max label address seen in the stream so far
    ticks: int                # stream ticks processed when this was taken
    events: int               # spike events consumed when this was taken
    final: bool = False       # True only for SessionHandle.result()
    status: ServeStatus = ServeStatus.OK


class _Session:
    """Host bookkeeping for one open session (internal to the engine)."""

    __slots__ = (
        "sid", "slot", "meta", "sp_tick", "sp_addr", "sp_ptr", "cursor",
        "max_fed_tick", "label", "label_tick", "label_seen", "end_seen",
        "end_tick", "closed", "n_events", "t_open", "t_last", "snapshot",
        "offloaded", "queued", "gate_label", "model_id", "status",
        "deadline", "retries", "t_queued",
    )

    def __init__(
        self,
        sid: int,
        now: float,
        meta: Optional[dict] = None,
        model_id: str = "default",
    ):
        self.sid = sid
        # Which registered model's network (and therefore which per-model
        # carry pool / stream packer) this stream runs against — state
        # shapes differ per model, so a session is pinned to its model's
        # pool for life.
        self.model_id = model_id
        self.slot: Optional[int] = None    # pool row; None ⇒ offloaded/new
        self.meta = meta
        # pending spike events (absolute ticks, tick-ordered); consumed by
        # advancing sp_ptr, compacted on feed
        self.sp_tick = np.zeros(0, np.int64)
        self.sp_addr = np.zeros(0, np.int64)
        self.sp_ptr = 0
        self.cursor = 0            # next stream tick to process
        self.max_fed_tick = -1     # largest tick any fed word carried
        self.label = 0             # running max of label addresses (decode_events_host semantics)
        self.label_tick = 0
        self.label_seen = False
        self.end_seen = False
        self.end_tick = 0
        self.closed = False
        self.n_events = 0
        self.t_open = now
        self.t_last = now
        self.snapshot: Optional[SessionSnapshot] = None
        self.offloaded: Optional[Dict[str, np.ndarray]] = None
        self.queued = False        # True while sitting in the packer's queue
        self.t_queued: Optional[float] = None  # entered the queue (repro.obs)
        self.status = ServeStatus.OK   # FAULT once quarantined (sticky)
        self.deadline: Optional[float] = None  # absolute; None = no deadline
        self.retries = 0           # launch-fault rewinds since last success
        # With infer_window == "valid" the readout window starts at the label
        # announcement, so ticks fed *before* the (single) label word cannot
        # know their final valid bit — the engine sets this flag to hold the
        # stream back until the label (or END/close) arrives, after which the
        # incremental mask is exact.  "all"-window engines leave it False.
        self.gate_label = False

    # ------------------------------------------------------------- feeding

    def feed(self, events: np.ndarray) -> int:
        """Append one AER word buffer.  Words must be tick-ordered within a
        buffer and non-decreasing across buffers (the stream contract).
        Returns the number of spike events admitted."""
        if self.closed:
            raise StreamContractError(
                f"session {self.sid}: feed() on a closed session"
            )
        words = np.asarray(events, np.uint32).ravel()
        kind = words >> 24
        live = kind != 0
        words, kind = words[live], kind[live]
        if words.size == 0:
            return 0
        addr = ((words >> 12) & MAX_ADDR).astype(np.int64)
        tick = (words & MAX_TICK).astype(np.int64)
        sp = kind == EVT_SPIKE
        if sp.any():
            # drop already-processed ticks (stream-contract violations) so
            # the pending arrays stay sorted relative to the cursor
            keep = sp & (tick >= self.cursor)
            self.sp_tick = np.concatenate(
                [self.sp_tick[self.sp_ptr:], tick[keep]]
            )
            self.sp_addr = np.concatenate(
                [self.sp_addr[self.sp_ptr:], addr[keep]]
            )
            self.sp_ptr = 0
            self.n_events += int(keep.sum())
        lab = kind == EVT_LABEL
        if lab.any():
            self.label = max(self.label, int(addr[lab].max()))
            self.label_tick = max(self.label_tick, int(tick[lab].max()))
            self.label_seen = True
        end = kind == EVT_END
        if end.any():
            self.end_seen = True
            self.end_tick = max(self.end_tick, int(tick[end].max()))
        self.max_fed_tick = max(self.max_fed_tick, int(tick.max()))
        return int(sp.sum())

    # ---------------------------------------------------------- scheduling

    def horizon(self) -> int:
        """First tick that is *not* yet processable.  END pins the stream
        length; a closed END-less stream runs to the last fed tick; an open
        stream holds back its newest tick (a later feed may still add words
        at it)."""
        if self.end_seen:
            return self.end_tick + 1
        if self.closed:
            return self.max_fed_tick + 1
        if self.gate_label and not self.label_seen:
            # Supervised readout window undetermined: a label word arriving
            # later would retroactively invalidate any tick processed now.
            return 0
        return max(self.max_fed_tick, 0)

    def processable(self) -> int:
        return max(0, self.horizon() - self.cursor)

    def take_chunk(self, num_ticks: int) -> "SessionChunkRef":
        """Consume up to ``num_ticks`` processable ticks from the cursor —
        the per-session half of building one tick-tile."""
        n = min(self.processable(), num_ticks)
        base = self.cursor
        end = base + n
        hi = int(np.searchsorted(self.sp_tick[self.sp_ptr:], end)) + self.sp_ptr
        ref = SessionChunkRef(
            sp_tick=self.sp_tick[self.sp_ptr:hi],
            sp_addr=self.sp_addr[self.sp_ptr:hi],
            base=base,
            n_live=n,
            label_tick=self.label_tick,
            end_tick=self.end_tick if self.end_seen else None,
        )
        self.sp_ptr = hi
        self.cursor = end
        return ref

    def restore_chunk(self, ref: "SessionChunkRef") -> None:
        """Undo a :meth:`take_chunk` whose tile launch failed: re-prepend
        the chunk's spikes and rewind the cursor so the ticks are re-served
        on the next pack.  Safe against interleaved feeds — anything fed
        after the take carries ticks ``>= ref.base + n_live`` (feed drops
        ticks behind the cursor), so prepending preserves sort order."""
        self.sp_tick = np.concatenate(
            [ref.sp_tick, self.sp_tick[self.sp_ptr:]]
        )
        self.sp_addr = np.concatenate(
            [ref.sp_addr, self.sp_addr[self.sp_ptr:]]
        )
        self.sp_ptr = 0
        self.cursor = ref.base


@dataclasses.dataclass
class SessionChunkRef:
    """One session's slice of a tick-tile: the spikes and masks of stream
    ticks ``[base, base + n_live)``, in absolute tick coordinates
    (:func:`repro.serve.batching.decode_session_chunks` rebases them)."""

    sp_tick: np.ndarray
    sp_addr: np.ndarray
    base: int
    n_live: int                    # dynamics run for ticks < base + n_live
    label_tick: int                # valid from label_tick + label_delay
    end_tick: Optional[int]        # valid through end_tick; None = END unseen


class SessionPool:
    """``S_cap`` device-resident carry rows + admission control.

    The pool owns the state pytree as ``(S_cap + 1, ·)`` arrays — row
    ``S_cap`` is the trash slot every padded tile lane gathers from and
    scatters to, so tile launches never change shape with occupancy.
    Scatters are applied *functionally at launch time* (``state = state.at
    [idx].set(new)`` on the not-yet-ready device values), so ``self.state``
    always reflects every launched tile and eviction needs no in-flight
    tracking: offloading a row merely blocks until the chain resolves.

    Admission control: :meth:`place` seats a batch of sessions, evicting
    least-recently-*packed* residents when full (skipping sessions being
    seated right now); :meth:`sweep` offloads residents idle longer than
    ``idle_timeout``.  Both take their notion of time from the injected
    ``clock`` so policies unit-test against a scripted clock.
    """

    def __init__(
        self,
        backend,                     # repro.core.backend.ExecutionBackend
        capacity: int,
        idle_timeout: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if capacity < 1:
            raise ValueError(f"pool capacity must be >= 1, got {capacity}")
        self.backend = backend
        self.capacity = int(capacity)
        self.trash = self.capacity          # fixed trash row index
        self.idle_timeout = idle_timeout
        self._clock = clock
        self.state = backend.init_session_state(self.capacity + 1)
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self._resident: "OrderedDict[int, _Session]" = OrderedDict()
        self.evictions = 0
        self.readmissions = 0

    # ------------------------------------------------------------ residency

    def __len__(self) -> int:
        return len(self._resident)

    def touch(self, sess: _Session) -> None:
        """Mark a resident session most-recently-used."""
        if sess.sid in self._resident:
            self._resident.move_to_end(sess.sid)
        sess.t_last = self._clock()

    def place(
        self, sessions: List[_Session]
    ) -> Tuple[np.ndarray, Optional[Dict[str, np.ndarray]]]:
        """Seat every session (allocating/evicting as needed) and return
        ``(slots, admit_rows)``: the slot index per session, plus the stacked
        host rows to scatter for the newly seated ones (``None`` when all
        were already resident).  New sessions admit zero rows — a freed slot
        still holds its previous occupant's state, so the scatter is what
        resets it."""
        seating = {s.sid for s in sessions}
        admits: List[Tuple[int, _Session]] = []
        for i, sess in enumerate(sessions):
            if sess.slot is None:
                sess.slot = self._alloc(exclude=seating)
                admits.append((i, sess))
                if sess.offloaded is not None:
                    self.readmissions += 1
                self._resident[sess.sid] = sess
            self.touch(sess)
        slots = np.array([s.slot for s in sessions], np.int32)
        if not admits:
            return slots, None
        zeros = {
            k: np.zeros(v.shape[1:], np.float32) for k, v in self.state.items()
        }
        rows = {
            k: np.stack([
                (s.offloaded or zeros)[k] for _, s in admits
            ]) for k in STATE_KEYS
        }
        rows["idx"] = np.array([s.slot for _, s in admits], np.int32)
        for _, s in admits:
            s.offloaded = None
        return slots, rows

    def _alloc(self, exclude=()) -> int:
        if self._free:
            return self._free.pop()
        for sid, cand in self._resident.items():   # LRU order: oldest first
            if sid not in exclude:
                self.evict(cand)
                return self._free.pop()
        raise RuntimeError(
            f"session pool over capacity ({self.capacity}): every resident "
            "session is in the tile being placed"
        )

    def evict(self, sess: _Session) -> None:
        """Offload one resident session's carry row to host memory and free
        its slot.  Bit-exact: the row is copied verbatim (in quantized mode
        these are integers on the membrane grid), so readmission continues
        the stream as if never interrupted."""
        if sess.slot is None:
            raise RuntimeError(f"evict() on non-resident session {sess.sid}")
        sess.offloaded = {
            k: np.asarray(v[sess.slot]) for k, v in self.state.items()
        }
        self._free.append(sess.slot)
        sess.slot = None
        self._resident.pop(sess.sid, None)
        self.evictions += 1

    def release(self, sess: _Session) -> None:
        """Close-path slot return: the session is done, its state is dead."""
        if sess.slot is not None:
            self._free.append(sess.slot)
            sess.slot = None
            self._resident.pop(sess.sid, None)
        sess.offloaded = None

    def sweep(self, now: Optional[float] = None) -> int:
        """Evict residents idle longer than ``idle_timeout``; returns the
        number offloaded.  No-op when no timeout is configured."""
        if self.idle_timeout is None:
            return 0
        now = self._clock() if now is None else now
        stale = [
            s for s in self._resident.values()
            if now - s.t_last > self.idle_timeout
        ]
        for s in stale:
            self.evict(s)
        return len(stale)

    # --------------------------------------------------------- device state

    def padded_slots(self, slots: np.ndarray, b_pad: int) -> jax.Array:
        """Slot vector padded to the tile's fixed lane count with the trash
        row, so gather/scatter programs see one shape per tile size."""
        idx = np.full((b_pad,), self.trash, np.int32)
        idx[: len(slots)] = slots
        return jax.numpy.asarray(idx)

    def gather(self, idx: jax.Array) -> Dict[str, jax.Array]:
        """Carry rows for one tile's lanes (trash lanes read garbage — their
        ``live``/``valid`` masks are zero, so it never propagates)."""
        return _gather(self.state, idx)

    def scatter(self, idx: jax.Array, new_state: Dict[str, jax.Array]) -> None:
        """Write one tile's final carries back (enqueued immediately — the
        pool state chains on the launch without host synchronisation)."""
        self.state = _scatter(self.state, idx, new_state)

    def admit(self, rows: Dict[str, np.ndarray]) -> None:
        """One batched scatter seating all of a tile's newly placed sessions
        (zeros for fresh sessions, offloaded rows for readmissions)."""
        idx = jax.numpy.asarray(rows["idx"])
        new = {k: jax.numpy.asarray(rows[k]) for k in STATE_KEYS}
        self.state = _scatter(self.state, idx, new)

    def state_bytes(self) -> int:
        """Device bytes the pool occupies (the S_cap capacity unit)."""
        return sum(v.size * v.dtype.itemsize for v in self.state.values())


@jax.jit
def _gather(state, idx):
    return {k: v[idx] for k, v in state.items()}


@jax.jit
def _scatter(state, idx, new):
    # duplicate trash-lane indices are fine: last-write-wins into a row
    # nothing ever reads as signal
    return {k: state[k].at[idx].set(new[k]) for k in state}
