"""Session-first serving engine over the shared execution backend.

This is the serving half of the paper's host↔accelerator split: where
:class:`repro.core.controller.OnlineLearner` drives ReckOn sample-by-sample
or batch-by-batch through an
:class:`~repro.core.backend.ExecutionBackend`, the engine drives the *same*
backend object for unbounded AER event *streams* — the paper's neuromorphic
edge scenario, where per-user traffic never arrives as whole padded
samples.

The primary model is the **session**: ``engine.open_session()`` returns a
:class:`SessionHandle`; ``handle.feed(events)`` appends AER words to the
stream; the engine's pump packs whichever sessions have processable ticks
into fixed-shape tick-tiles (:class:`repro.serve.scheduler.StreamPacker` —
continuous batching), gathers their device-resident carry state from the
:class:`repro.serve.session.SessionPool`, launches the backend's
``step_sessions`` op (carry in / carry out) and scatters updated state
back; ``handle.poll()`` returns incremental readout snapshots and
``handle.result()`` the final classification.  The historical whole-sample
path (``submit()`` / ``serve()`` over complete event buffers, bucketed by
:class:`repro.serve.scheduler.BucketingScheduler`) is retained as a thin
open-feed-close wrapper over the same session machinery — existing callers
run unmodified, with identical results.

**Multi-model serving** (the paper's runtime reprogrammability — one
fabric, many SRAM programs): an engine constructed with ``registry=``
serves every model in a :class:`~repro.serve.registry.ModelRegistry`
concurrently.  Each registered model gets its own *lane* — scheduler,
stream packer and carry pool (state shapes differ per network) — so every
tile stays single-model, like one SRAM image per chip program; the pump
loop interleaves launches across lanes, and request ids stay unique and
admission-ordered engine-wide through one shared allocator.  ``submit``,
``open_session``, ``serve`` and ``warmup`` route by ``model_id``
(defaulting to the first registered model), results carry their model id,
and :class:`ServeStats`/:class:`StreamStats` break out per-model.  The
classic single-model constructor ``BatchedEngine(cfg, params)`` is the
one-lane special case: it builds a private registry under the
``"default"`` id.

Backend dispatch (``"kernel"`` = fused Pallas kernels, ``"scan"`` = the
reference ``lax.scan``, ``"auto"`` = kernel on TPU / scan elsewhere) lives in
:mod:`repro.core.backend`, not here; the engine just submits tiles.  Weights
are jit *arguments*, not closure constants, so
:meth:`BatchedEngine.update_weights` (serving a network that is still
learning online) never recompiles — and because an
:class:`~repro.core.backend.ExecutionBackend` instance can be passed in
directly (``BatchedEngine.from_learner`` does exactly that), the engine and
a live :class:`~repro.core.controller.OnlineLearner` share one jit cache:
train, swap weights, serve, no recompile.  Models whose configs fall in the
same execution bucket share one pooled backend, so a multi-model engine
compiles each tile shape once, not once per model.

Quantized serving: when the backend runs the hardware-equivalence mode
(``cfg.neuron.quant`` / ``ExecutionBackend(quant=...)``), the engine is the
software twin of the FPGA serving path — every tile executes ReckOn's
fixed-point datapath, ``update_weights`` snaps incoming weights onto the
8-bit SRAM grid (the "SRAM load", so serving a float learner's live master
weights is still well-defined), and returned logits are the chip's
membrane-grid readout accumulators (argmax unchanged).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import (
    BackendLike,
    CompileError,
    ExecutionBackend,
    RuntimeConfig,
)
from repro.core.rsnn import RSNNConfig
from repro.kernels import traffic
from repro.serve import batching
from repro.serve.guard import (
    GuardConfig,
    GuardError,
    OverloadError,
    QuotaExceededError,
    ServeStatus,
    bad_rows,
    validate_events,
)
from repro.serve.registry import DEFAULT_MODEL, ModelRegistry, ModelSpec
from repro.serve.scheduler import (
    BatchTile,
    BucketingScheduler,
    ServeRequest,
    StreamPacker,
)
from repro.serve.session import SessionPool, SessionSnapshot, _Session


@dataclasses.dataclass
class ServeResult:
    """Per-request classification + accounting.

    ``status`` is the error model: :data:`~repro.serve.guard.ServeStatus.OK`
    results carry live logits; REJECTED (guard/overload/shed), EXPIRED
    (deadline passed before launch) and FAULT (numeric quarantine or an
    unrecoverable lane fault) results carry ``pred == -1`` and zero logits —
    dropped work surfaces as a typed result, never as a silent hole or an
    engine-killing exception."""

    rid: int
    pred: int                 # argmax class; -1 when status != OK
    logits: np.ndarray        # accumulated LI readout acc_y, shape (n_out,)
    label: int                # label carried by the AER stream (0 if absent)
    latency_s: float          # admission → result delivery (harvest); see
                              # BatchedEngine.serve — delivery lag behind
                              # device completion is bounded by the polling
                              # cadence and max_inflight_tiles; for non-OK
                              # results: admission → drop decision
    bucket_ticks: int         # padded tick length served at
    batch_size: int           # live samples in the tile
    model_id: str = DEFAULT_MODEL   # which registered model served it
    status: ServeStatus = ServeStatus.OK


@dataclasses.dataclass
class ServeStats:
    requests: int
    batches: int
    wall_s: float
    samples_per_sec: float
    p50_latency_s: float
    p99_latency_s: float
    mean_batch: float
    compiled_shapes: int
    # Analytic HBM bytes the served tiles streamed when the kernel backend
    # runs (:func:`repro.kernels.traffic.infer_fused_bytes` — one (B, O)
    # logits tile per batch instead of seven (T, B, ·) tensors); 0 on the
    # scan backend, which runs no Pallas tile.
    hbm_bytes_streamed: int = 0
    # Error-model counters: how many of `requests` ended non-OK (shed is
    # the subset of rejected evicted by the admission="shed" policy), and
    # how many lane restarts the window absorbed.
    rejected: int = 0
    expired: int = 0
    quarantined: int = 0
    shed: int = 0
    lane_restarts: int = 0
    # model_id → ServeStats for that model's slice of the run; populated by
    # serve() when the window touched more than one model, else None.
    per_model: Optional[Dict[str, "ServeStats"]] = None

    @classmethod
    def collect(
        cls,
        results: List[ServeResult],
        wall_s: float,
        batches: int,
        shapes: int,
        hbm_bytes: int = 0,
        shed: int = 0,
        lane_restarts: int = 0,
    ) -> "ServeStats":
        # Throughput and latency are computed over the *served* (OK)
        # results: a rejected request is decided in microseconds and would
        # otherwise inflate samples/s and deflate the percentiles.
        ok = [r for r in results if r.status is ServeStatus.OK]
        lat = np.array([r.latency_s for r in ok]) if ok else np.zeros(1)
        by = {
            s: sum(1 for r in results if r.status is s) for s in ServeStatus
        }
        return cls(
            requests=len(results),
            batches=batches,
            wall_s=wall_s,
            samples_per_sec=len(ok) / wall_s if wall_s > 0 else float("inf"),
            p50_latency_s=float(np.percentile(lat, 50)),
            p99_latency_s=float(np.percentile(lat, 99)),
            mean_batch=(len(ok) / batches) if batches else 0.0,
            compiled_shapes=shapes,
            hbm_bytes_streamed=hbm_bytes,
            rejected=by[ServeStatus.REJECTED],
            expired=by[ServeStatus.EXPIRED],
            quarantined=by[ServeStatus.FAULT],
            shed=shed,
            lane_restarts=lane_restarts,
        )


@dataclasses.dataclass
class _PendingTile:
    """A launched-but-unsynchronised batch tile: the device is still (or may
    still be) computing ``acc_y`` while the host moves on to later buckets."""

    acc_y: jax.Array          # (b_pad, n_out) device array, possibly in flight
    labels: np.ndarray
    tile: BatchTile
    b_live: int
    lane: "_ModelLane"

    def ready(self) -> bool:
        """Non-blocking readiness probe (conservative where unsupported)."""
        is_ready = getattr(self.acc_y, "is_ready", None)
        return bool(is_ready()) if callable(is_ready) else False


@dataclasses.dataclass
class _PendingStreamTile:
    """A launched-but-unharvested streaming tick-tile: the device may still
    be computing while the host packs the next tile."""

    acc_y: jax.Array                 # (b_pad, n_out) post-chunk accumulators
    lanes: List[Tuple["_Session", int, int]]   # (session, ticks, events) at launch
    t_launch: float
    num_ticks: int
    lane: "_ModelLane"

    def ready(self) -> bool:
        is_ready = getattr(self.acc_y, "is_ready", None)
        return bool(is_ready()) if callable(is_ready) else False


@dataclasses.dataclass
class StreamStats:
    """Streaming-serving throughput/latency accounting (one pump window)."""

    sessions: int                 # sessions that advanced in the window
    tiles: int                    # tick-tiles launched
    events: int                   # spike events consumed
    ticks: int                    # live session-ticks advanced (Σ chunk lengths)
    wall_s: float
    events_per_sec: float         # over wall_s - admission_wait_s: device
                                  # throughput, not caller stall (see below)
    ticks_per_sec: float
    p50_tile_latency_s: float     # launch → harvest per tick-tile
    p99_tile_latency_s: float
    mean_lanes: float             # live lanes per tile (packing efficiency)
    evictions: int
    readmissions: int
    compiled_shapes: int          # distinct step_sessions (T, B) programs
    hbm_bytes_streamed: int = 0
    # Error-model counters (window totals).
    rejected: int = 0             # feeds refused by the guard / overload
    expired: int = 0              # sessions dropped at pack time (deadline)
    shed: int = 0                 # requests evicted by admission="shed"
    quarantined: int = 0          # sessions FAULTed by health checks/faults
    lane_restarts: int = 0        # backend rebuilds the window absorbed
    saturation_storms: int = 0    # quantized rows that escaped the 12-bit grid
    # Wall time callers spent blocked on a full bounded packer queue (the
    # engine pumps inline to make room).  Subtracted from wall_s for
    # events_per_sec/ticks_per_sec so throughput under backpressure
    # reports what the device sustained, not how long callers stalled.
    admission_wait_s: float = 0.0
    # model_id → StreamStats for that model's lane; populated when the
    # engine serves more than one model, else None.
    per_model: Optional[Dict[str, "StreamStats"]] = None


class _ModelLane:
    """Per-model serving state inside a :class:`BatchedEngine`.

    One lane per registered model: its own :class:`BucketingScheduler`
    (whole-sample buckets), :class:`StreamPacker` (streaming ready-queue)
    and :class:`SessionPool` (carry shapes differ per network, so pools
    cannot be shared), plus the model-attributed traffic counters.  Tiles
    never mix models — a launch reads exactly one SRAM image, like the
    chip — but the engine pump interleaves launches across lanes.
    """

    def __init__(self, engine: "BatchedEngine", spec: ModelSpec):
        self.spec = spec
        cfg, be = spec.cfg, spec.backend
        budget = be.vmem_budget
        self.max_batch = engine._max_batch or batching.max_batch_for(
            cfg, budget, num_devices=be.num_devices
        )
        # per-kernel-tile rows, for the analytic HBM traffic accounting
        self.tile_rows = batching.max_batch_for(cfg, budget)
        self.scheduler = BucketingScheduler(
            self.max_batch, engine.tick_granularity, clock=engine._clock,
            rid_alloc=engine._alloc_rid,
            max_pending=engine._max_pending, admission=engine._admission,
        )
        # Pool capacity must seat one full tile of sessions at once; the
        # trash row on top keeps gather/scatter shapes fixed.
        capacity = max(
            engine._max_sessions or batching.max_sessions_for(cfg),
            self.max_batch,
        )
        self.pool = SessionPool(
            be, capacity, idle_timeout=engine._idle_timeout,
            clock=engine._clock,
        )
        self.packer = StreamPacker(
            self.max_batch, tick_tile=engine._tick_tile,
            tick_granularity=engine.tick_granularity,
            max_pending=engine._max_pending_sessions,
        )
        # Per-lane guard: the engine-wide policy with this model's n_in
        # resolved; None when the engine was built with guard=False.
        self.guard: Optional[GuardConfig] = (
            engine._guard.for_model(cfg.n_in)
            if engine._guard is not None else None
        )
        self.zero_states: Dict[int, Dict[str, jax.Array]] = {}
        self.tile_lat: List[float] = []
        # Dropped-work results (REJECTED/EXPIRED/FAULT) accumulated outside
        # a serve() window — drained by BatchedEngine.take_dead_results().
        self.dead: List[ServeResult] = []
        self.reset_counters()

    @property
    def model_id(self) -> str:
        return self.spec.model_id

    @property
    def cfg(self) -> RSNNConfig:
        return self.spec.cfg

    @property
    def backend(self) -> ExecutionBackend:
        return self.spec.backend

    @property
    def weights(self) -> Dict[str, jax.Array]:
        """The live SRAM image — fetched per launch, so a registry hot-swap
        applies to the very next tile."""
        return self.spec.weights

    def reset_counters(self) -> None:
        self.tile_lat.clear()
        self.bytes_streamed = 0
        self.tiles = 0
        self.events = 0
        self.ticks = 0
        self.lanes = 0
        self.rejected = 0
        self.expired = 0
        self.shed = 0
        self.quarantined = 0
        self.lane_restarts = 0
        self.saturation_storms = 0
        self.admission_wait_s = 0.0

    def zero_state(self, b_pad: int):
        """Cached zero-carry pytree per tile width (a read-only jit input,
        so reusing it across launches is safe)."""
        st = self.zero_states.get(b_pad)
        if st is None:
            st = self.zero_states[b_pad] = self.backend.init_session_state(
                b_pad
            )
        return st

    def account_tile_bytes(self, num_ticks: int, b_pad: int, fn) -> None:
        """Attribute one kernel launch's analytic HBM bytes to this lane
        (scan runs no Pallas tile, so nothing is attributed)."""
        if self.backend.backend != "kernel":
            return
        cfg = self.cfg
        ndev = self.backend.num_devices
        shard_b = -(-b_pad // ndev)
        self.bytes_streamed += ndev * fn(
            num_ticks, shard_b, cfg.n_in, cfg.n_hid, cfg.n_out,
            batch_tile=self.tile_rows,
        )


class SessionHandle:
    """The public face of one open stream (from ``engine.open_session()``).

    ``feed`` appends AER words (ticks non-decreasing across feeds — the
    stream contract); the engine processes them when its pump next packs
    this session into a tick-tile (``engine.pump()``, or implicitly via
    :meth:`result`).  ``poll`` is non-blocking and returns the latest
    harvested :class:`~repro.serve.session.SessionSnapshot` (or ``None``);
    ``result`` closes the stream, drains every pending tick and returns the
    final snapshot; ``close`` abandons the stream and frees its pool slot.
    """

    def __init__(self, engine: "BatchedEngine", sess: _Session):
        self._engine = engine
        self._sess = sess

    @property
    def sid(self) -> int:
        return self._sess.sid

    @property
    def model_id(self) -> str:
        return self._sess.model_id

    @property
    def closed(self) -> bool:
        return self._sess.closed

    @property
    def status(self) -> ServeStatus:
        """OK while the stream is healthy; FAULT once quarantined (numeric
        health check or unrecoverable lane fault), EXPIRED once its
        deadline dropped it — both terminal."""
        return self._sess.status

    def feed(self, events: np.ndarray) -> int:
        """Append one AER word buffer; returns spike events admitted.  Does
        not launch work — call ``engine.pump()`` (or :meth:`result`) to
        advance.  Raises a typed
        :class:`~repro.serve.guard.GuardError` subclass when the buffer
        fails validation, exceeds a quota, or the session is closed /
        quarantined — the session itself is untouched by a rejected feed."""
        return self._engine._feed(self._sess, events)

    def poll(self) -> Optional[SessionSnapshot]:
        """Latest incremental readout snapshot, non-blocking."""
        self._engine._harvest_stream(block=False)
        return self._sess.snapshot

    def result(self) -> SessionSnapshot:
        """Close the stream, process every fed tick, return the final
        classification (synchronises)."""
        return self._engine._finish_session(self._sess)

    def close(self) -> None:
        """Abandon the stream: unprocessed events are dropped and the pool
        slot is freed.  Use :meth:`result` to finish instead."""
        self._engine._abandon_session(self._sess)


class BatchedEngine:
    """Batched AER classification service over one or many registered models.

    Parameters
    ----------
    cfg:
        The network the weights belong to (e.g. ``Presets.braille(...)``) —
        the single-model convenience path, mutually exclusive with
        ``registry``.
    params:
        ``{"w_in", "w_rec", "w_out"}`` (+ optional scalar ``"alpha"``) — the
        same pytree :class:`~repro.core.controller.OnlineLearner` trains.
    registry:
        A :class:`~repro.serve.registry.ModelRegistry` to serve instead of a
        single ``(cfg, params)`` pair: every registered model becomes
        routable via the ``model_id=`` arguments (models registered *after*
        construction too — lanes materialise on first use).  The first
        registered model (or ``model_id`` when given) is the default route.
    model_id:
        The id the single-model path registers under, and the default route
        for calls that don't pass ``model_id=``.
    backend:
        ``"kernel" | "scan" | "auto"``, or an existing
        :class:`~repro.core.backend.ExecutionBackend` to share its jit cache
        (the online-learning-while-serving configuration).  With
        ``registry=`` each model already resolved its own pooled backend,
        so this is ignored.
    max_batch:
        Admission size per tile; defaults to one full per-device kernel tile
        times the data-parallel device count
        (:func:`repro.serve.batching.max_batch_for`).  The kernels batch-tile
        internally, so this is a scheduling knob, not a VMEM cap.  Applies
        per lane (an explicit value caps every model's tiles).
    mesh:
        Data-parallel serving: a mesh whose data axes the backend shards
        every inference tile's sample axis over (weights replicated) —
        admission scales with the device count.
    max_sessions:
        Streaming capacity ``S_cap`` — resident sessions each model's
        device pool holds; defaults to
        :func:`repro.serve.batching.max_sessions_for`'s byte-budget sizing
        per model.  Sessions beyond it are LRU-evicted to host memory
        (bit-exact) and readmitted on their next packed tile.
    idle_timeout:
        Seconds of inactivity after which a resident session is offloaded
        (``None`` disables the sweep).
    tick_tile:
        Fixed tick length of streaming tiles (latency-bounded mode).  When
        ``None``, each packed tile drains everything its sessions have
        pending (throughput mode — also what the whole-sample ``serve()``
        wrapper uses).
    runtime:
        A :class:`~repro.core.backend.RuntimeConfig` bundling the
        backend/quant/vmem_budget/mesh knobs (the loose kwargs remain as a
        deprecated passthrough; resolution happens in ``as_backend``).
    guard:
        Input-validation policy: a
        :class:`~repro.serve.guard.GuardConfig` (per-lane ``n_in`` is
        filled from each model's config), ``None`` for the default policy,
        or ``False`` to disable validation entirely (the overhead-bench
        escape hatch — production callers should not).
    max_pending / admission:
        Bounded whole-sample admission queue per lane.  ``max_pending``
        caps queued requests (``None`` = unbounded, the legacy behaviour);
        on overflow ``admission="reject"`` raises
        :class:`~repro.serve.guard.OverloadError` at ``submit()`` while
        ``"shed"`` evicts the *oldest* queued request, which surfaces as a
        REJECTED result.
    default_deadline_s:
        Relative deadline stamped on every admitted request that doesn't
        pass its own ``deadline_s``; expired requests are dropped at pack
        time (before any launch) and surface as EXPIRED results.  ``None``
        disables.
    max_pending_sessions:
        Bounds each lane's streaming ready-queue (sessions).  A ``feed``
        that would overflow it pumps the lane inline until there is room —
        that stall is *admission wait*, excluded from StreamStats
        throughput.
    session_deadline_s:
        Relative deadline stamped on every ``open_session`` that doesn't
        pass its own; checked at pack time — an expired session is dropped
        before launch with a terminal EXPIRED snapshot.
    max_tile_retries:
        Launch-fault budget: how many times faulted work is rewound and
        relaunched (through a lane restart) before the affected
        requests/sessions are FAULTed.  A program that fails to build
        (:class:`~repro.core.backend.CompileError`) is not a fault: it is
        raised out of ``serve``/``pump`` on the first launch.
    fault_hook:
        Test/chaos injection point: called as ``fault_hook(model_id,
        kind)`` (``kind ∈ {"tile", "stream"}``) at the top of every launch,
        *before* any state mutation; an exception it raises is handled
        exactly like a device launch fault.  Leave ``None`` in production.
    """

    def __init__(
        self,
        cfg: Optional[RSNNConfig] = None,
        params: Optional[Dict[str, jax.Array]] = None,
        *,
        registry: Optional[ModelRegistry] = None,
        model_id: str = DEFAULT_MODEL,
        backend: BackendLike = "auto",
        max_batch: Optional[int] = None,
        tick_granularity: int = 32,
        vmem_budget: Optional[int] = None,
        mesh=None,
        max_inflight_tiles: int = 8,
        clock: Callable[[], float] = time.monotonic,
        max_sessions: Optional[int] = None,
        idle_timeout: Optional[float] = None,
        tick_tile: Optional[int] = None,
        runtime: Optional[RuntimeConfig] = None,
        guard: Union[GuardConfig, None, bool] = None,
        max_pending: Optional[int] = None,
        admission: str = "reject",
        default_deadline_s: Optional[float] = None,
        max_pending_sessions: Optional[int] = None,
        session_deadline_s: Optional[float] = None,
        max_tile_retries: int = 3,
        fault_hook: Optional[Callable[[str, str], None]] = None,
    ):
        self.tick_granularity = tick_granularity
        # Backpressure for the deferred-sync serve loop: at most this many
        # launched-but-unharvested tiles (each pins its raster + acc_y device
        # buffers) before the host blocks on the oldest.
        self.max_inflight_tiles = max(1, int(max_inflight_tiles))
        self._clock = clock
        self._max_batch = max_batch
        self._max_sessions = max_sessions
        self._idle_timeout = idle_timeout
        self._tick_tile = tick_tile
        if guard is False:
            self._guard: Optional[GuardConfig] = None
        elif guard is None or guard is True:
            self._guard = GuardConfig()
        else:
            self._guard = guard
        self._max_pending = max_pending
        self._admission = admission
        self._default_deadline_s = default_deadline_s
        self._max_pending_sessions = max_pending_sessions
        self._session_deadline_s = session_deadline_s
        self._max_tile_retries = max(0, int(max_tile_retries))
        self._fault_hook = fault_hook
        self._next_rid = 0
        if registry is None:
            if cfg is None or params is None:
                raise ValueError(
                    "BatchedEngine needs either (cfg, params) or registry="
                )
            registry = ModelRegistry()
            registry.register(
                model_id, cfg, params, backend=backend, runtime=runtime,
                vmem_budget=vmem_budget, mesh=mesh,
            )
        else:
            if cfg is not None or params is not None:
                raise ValueError(
                    "pass either (cfg, params) or registry=, not both"
                )
            if len(registry) == 0:
                raise ValueError("registry has no registered models")
        self.registry = registry
        if model_id in registry:
            self.default_model = model_id
        elif model_id == DEFAULT_MODEL:
            self.default_model = registry.ids()[0]
        else:
            registry.get(model_id)   # raises KeyError naming the options
        self._lanes: Dict[str, _ModelLane] = {}
        self._sessions: Dict[int, _Session] = {}
        self._next_sid = 0
        self._stream_pending: List[_PendingStreamTile] = []
        self._in_restart = False   # re-entrancy guard for lane restarts
        self._lane(self.default_model)   # default lane is always live

    # --------------------------------------------------------------- routing

    def _alloc_rid(self) -> int:
        """Engine-wide request ids: every lane's scheduler draws from this
        one counter, so rids stay unique and admission-ordered across
        models."""
        rid = self._next_rid
        self._next_rid += 1
        return rid

    def _lane(self, model_id: Optional[str] = None) -> _ModelLane:
        """The serving lane for a model (default route when ``None``),
        created on first use — so models registered after engine
        construction, e.g. by a learner publishing mid-serve, become
        routable with no engine-side setup."""
        mid = self.default_model if model_id is None else model_id
        lane = self._lanes.get(mid)
        if lane is None:
            lane = self._lanes[mid] = _ModelLane(self, self.registry.get(mid))
        return lane

    def model_ids(self) -> Tuple[str, ...]:
        """Models currently routable through this engine."""
        return self.registry.ids()

    # Single-model compatibility surface: the historical attributes resolve
    # against the default lane, so one-model callers (and the test suite's
    # whole-sample paths) are unchanged.

    @property
    def cfg(self) -> RSNNConfig:
        return self._lane().cfg

    @property
    def engine(self) -> ExecutionBackend:
        return self._lane().backend

    @property
    def backend(self) -> str:
        return self._lane().backend.backend

    @property
    def max_batch(self) -> int:
        return self._lane().max_batch

    @property
    def scheduler(self) -> BucketingScheduler:
        return self._lane().scheduler

    @property
    def packer(self) -> StreamPacker:
        return self._lane().packer

    @property
    def pool(self) -> SessionPool:
        return self._lane().pool

    @property
    def _weights(self) -> Dict[str, jax.Array]:
        return self._lane().weights

    @property
    def quantized(self) -> bool:
        """True when default-route tiles execute the fixed-point
        hardware-equivalence datapath (logits are then membrane-grid
        integers)."""
        return self._lane().backend.quant is not None

    @classmethod
    def from_learner(cls, learner, **kw) -> "BatchedEngine":
        """Serve an :class:`~repro.core.controller.OnlineLearner`'s network
        through the learner's own execution backend — shared jit cache, so
        ``update_weights(learner.weights)`` mid-training re-uses the exact
        programs the learner compiled (and vice versa)."""
        kw.setdefault("backend", learner.backend)
        return cls(learner.cfg, learner.inference_params(), **kw)

    def update_weights(
        self, weights: Dict[str, jax.Array], model_id: Optional[str] = None
    ) -> None:
        """Swap in newly-trained weights for one model (no recompilation —
        weights are jit arguments).  In quantized mode this is the SRAM
        load: weights are snapped onto the 8-bit grid, through a jit'd
        program that donates (and thus reuses) the previous SRAM image's
        buffers.  Delegates to
        :meth:`~repro.serve.registry.ModelRegistry.update_weights`, so a
        mis-shaped image fails loudly at the registry boundary."""
        self.registry.update_weights(
            self.default_model if model_id is None else model_id, weights
        )

    # ----------------------------------------------------------------- serving

    def _launch_tile(self, lane: _ModelLane, tile: BatchTile) -> _PendingTile:
        """Decode, pad and *launch* one batch tile — returns without
        synchronising on the device so consecutive buckets overlap host
        decode with device compute."""
        self._inject_fault(lane, "tile")
        cfg = lane.cfg
        events = [r.events for r in tile.requests]
        raster, valid, labels = batching.decode_events_host(
            events, cfg.n_in, tile.num_ticks, cfg.label_delay
        )
        b_live = len(events)
        b_pad = batching.padded_batch_size(b_live, lane.max_batch)
        raster, valid = batching.pad_batch(raster, valid, b_pad)
        # With a data mesh, every device fetches its own replicated weight
        # set and runs its (shard-padded) slice of the batch.
        lane.account_tile_bytes(
            tile.num_ticks, b_pad, traffic.infer_fused_tiled_bytes
        )
        out = lane.backend.inference(
            lane.weights, jnp.asarray(raster), jnp.asarray(valid)
        )
        return _PendingTile(
            acc_y=out["acc_y"], labels=labels, tile=tile, b_live=b_live,
            lane=lane,
        )

    def _finalize(self, pending: _PendingTile) -> List[ServeResult]:
        """Materialise one launched tile's results (synchronises on it).

        Per-sample numeric health runs here: a row carrying NaN/inf (or,
        quantized, a saturation storm off the 12-bit grid) becomes a FAULT
        result while its tile-mates are delivered unchanged.  A device
        fault surfacing at materialisation FAULTs the whole tile and
        restarts the lane."""
        lane = pending.lane
        try:
            acc_y = np.asarray(pending.acc_y)[: pending.b_live]
        except Exception:
            if not self._in_restart:
                self._restart_lane(lane)
            lane.quarantined += len(pending.tile.requests)
            return [
                self._dead_result(lane, req, ServeStatus.FAULT)
                for req in pending.tile.requests
            ]
        t_done = self._clock()
        bad, sat = bad_rows(
            acc_y, quant=lane.backend.quant, ticks=pending.tile.num_ticks
        )
        lane.saturation_storms += int(sat.sum())
        lane.quarantined += int(bad.sum())
        zeros = np.zeros((lane.cfg.n_out,), np.float32)
        return [
            ServeResult(
                rid=req.rid,
                pred=-1 if bad[i] else int(np.argmax(acc_y[i])),
                logits=zeros if bad[i] else acc_y[i],
                label=int(pending.labels[i]),
                latency_s=t_done - req.t_submit,
                bucket_ticks=pending.tile.num_ticks,
                batch_size=pending.b_live,
                model_id=lane.model_id,
                status=ServeStatus.FAULT if bad[i] else ServeStatus.OK,
            )
            for i, req in enumerate(pending.tile.requests)
        ]

    def run_tile(
        self, tile: BatchTile, model_id: Optional[str] = None
    ) -> List[ServeResult]:
        """Decode, pad, classify one batch tile; per-request results.  The
        tile must come from the same model's scheduler it is run under."""
        return self._finalize(self._launch_tile(self._lane(model_id), tile))

    def submit(
        self,
        events: np.ndarray,
        meta: Optional[dict] = None,
        model_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
    ) -> int:
        """Admit one AER sample for a registered model (default route when
        ``model_id`` is ``None``); returns its engine-unique request id.

        The buffer passes the lane's input guard first — a malformed or
        over-quota buffer raises a typed
        :class:`~repro.serve.guard.GuardError` subclass and admits nothing.
        A full bounded queue raises
        :class:`~repro.serve.guard.OverloadError` under
        ``admission="reject"``; under ``"shed"`` the oldest queued request
        is evicted instead (surfacing as a REJECTED result via
        :meth:`take_dead_results` / ``serve()``).  ``deadline_s`` is
        relative to now (falls back to the engine's ``default_deadline_s``).
        """
        lane = self._lane(model_id)
        events = self._validate_for(lane, events)
        rid = lane.scheduler.submit(
            events, meta, deadline=self._deadline(deadline_s)
        )
        self._collect_dropped(lane)
        return rid

    # ------------------------------------------------- guards + error model

    def _validate_for(self, lane: _ModelLane, events) -> np.ndarray:
        """Run one buffer through the lane's input guard (no-op when the
        engine was built with ``guard=False``)."""
        if lane.guard is None:
            return np.asarray(events)
        return validate_events(
            events, lane.guard, what=f"model {lane.model_id!r} buffer"
        )

    def _deadline(self, deadline_s: Optional[float]) -> Optional[float]:
        rel = (
            deadline_s if deadline_s is not None else self._default_deadline_s
        )
        return None if rel is None else self._clock() + rel

    def _dead_result(
        self, lane: _ModelLane, req: ServeRequest, status: ServeStatus
    ) -> ServeResult:
        """The typed tombstone for one dropped request."""
        return ServeResult(
            rid=req.rid,
            pred=-1,
            logits=np.zeros((lane.cfg.n_out,), np.float32),
            label=0,
            latency_s=self._clock() - req.t_submit,
            bucket_ticks=req.bucket,
            batch_size=0,
            model_id=lane.model_id,
            status=status,
        )

    def _collect_dropped(self, lane: _ModelLane) -> None:
        """Convert the lane's shed and deadline-expired requests into dead
        results (REJECTED / EXPIRED) — called at admission and pack time so
        expired work never occupies a launch slot."""
        for req in lane.scheduler.shed:
            lane.shed += 1
            lane.rejected += 1
            lane.dead.append(
                self._dead_result(lane, req, ServeStatus.REJECTED)
            )
        lane.scheduler.shed.clear()
        for req in lane.scheduler.take_expired():
            lane.expired += 1
            lane.dead.append(self._dead_result(lane, req, ServeStatus.EXPIRED))

    def take_dead_results(
        self, model_id: Optional[str] = None
    ) -> List[ServeResult]:
        """Drain the dropped-work results (REJECTED/EXPIRED/FAULT) for one
        model (or every lane) — the direct ``submit``/``run_tile`` caller's
        window into the error model; ``serve()`` drains them into its
        result list automatically."""
        lanes = (
            [self._lane(model_id)] if model_id is not None
            else list(self._lanes.values())
        )
        out: List[ServeResult] = []
        for lane in lanes:
            self._collect_dropped(lane)
            out.extend(lane.dead)
            lane.dead.clear()
        return out

    # ------------------------------------------------------ lane supervision

    def _inject_fault(self, lane: _ModelLane, kind: str) -> None:
        if self._fault_hook is not None:
            self._fault_hook(lane.model_id, kind)

    def _restart_lane(self, lane: _ModelLane) -> None:
        """Supervisor restart after a device/launch fault: materialise what
        is trustworthy, abandon the rest, rebuild.

        1. every *other* in-flight tile is harvested (their device buffers
           predate the fault);
        2. each resident session is evicted to a bit-exact host snapshot —
           one whose row cannot be materialised (poisoned chain) is
           quarantined instead;
        3. the registry swaps the lane's pooled backend for a freshly
           constructed one (fresh jit state; recompiles on next launch) and
           the lane gets a new pool, so no future launch touches old device
           buffers.  Healthy sessions re-seat from their snapshots on their
           next packed tile, bitwise identical to an undisturbed stream.
        """
        self._in_restart = True
        try:
            self._harvest_stream(block=True)
            for sess in list(lane.pool._resident.values()):
                try:
                    lane.pool.evict(sess)
                except Exception:
                    self._quarantine(lane, sess)
            old_pool = lane.pool
            lane.spec = self.registry.rebuild_backend(lane.model_id)
            lane.pool = SessionPool(
                lane.backend, old_pool.capacity,
                idle_timeout=old_pool.idle_timeout, clock=self._clock,
            )
            lane.pool.evictions = old_pool.evictions
            lane.pool.readmissions = old_pool.readmissions
            lane.zero_states.clear()
            lane.lane_restarts += 1
        finally:
            self._in_restart = False

    def _quarantine(self, lane: _ModelLane, sess: _Session) -> None:
        """Terminally FAULT one session: its stream state is not
        trustworthy, so it is closed with a dead snapshot while the rest of
        its tile (and lane) keeps serving."""
        if sess.status is ServeStatus.FAULT:
            return
        sess.status = ServeStatus.FAULT
        sess.closed = True
        sess.snapshot = SessionSnapshot(
            sid=sess.sid, pred=-1,
            logits=np.zeros((lane.cfg.n_out,), np.float32),
            label=sess.label, ticks=sess.cursor, events=sess.n_events,
            final=True, status=ServeStatus.FAULT,
        )
        lane.quarantined += 1
        try:
            lane.pool.release(sess)
        except Exception:
            sess.slot = None

    def _expire_session(self, lane: _ModelLane, sess: _Session) -> None:
        """Terminal EXPIRED drop at pack time: the session's deadline
        passed before its pending ticks launched."""
        sess.status = ServeStatus.EXPIRED
        sess.closed = True
        sess.snapshot = SessionSnapshot(
            sid=sess.sid, pred=-1,
            logits=np.zeros((lane.cfg.n_out,), np.float32),
            label=sess.label, ticks=sess.cursor, events=sess.n_events,
            final=True, status=ServeStatus.EXPIRED,
        )
        lane.expired += 1
        lane.pool.release(sess)

    # ---------------------------------------------------- session streaming

    def open_session(
        self,
        meta: Optional[dict] = None,
        model_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
    ) -> SessionHandle:
        """Open one AER event stream with persistent recurrent state.

        The session is pinned to its model's lane for life — its carry
        ``(v, z, y, acc_y, n_spk)`` lives in that model's device-resident
        :class:`~repro.serve.session.SessionPool` while hot (LRU-evicted to
        host bit-exactly under capacity pressure) — feed events in
        arbitrary increments; chunking never changes the result.

        ``deadline_s`` (relative; falls back to the engine's
        ``session_deadline_s``) bounds how long the stream may wait for
        device time: a session whose deadline passes before its pending
        ticks are packed is dropped at pack time with a terminal EXPIRED
        snapshot.
        """
        lane = self._lane(model_id)
        sess = _Session(
            self._next_sid, self._clock(), meta, model_id=lane.model_id
        )
        sess.gate_label = lane.cfg.eprop.infer_window == "valid"
        rel = (
            deadline_s if deadline_s is not None else self._session_deadline_s
        )
        sess.deadline = None if rel is None else self._clock() + rel
        self._next_sid += 1
        self._sessions[sess.sid] = sess
        return SessionHandle(self, sess)

    def _feed(self, sess: _Session, events: np.ndarray) -> int:
        lane = self._lanes[sess.model_id]
        if lane.guard is not None:
            try:
                events = validate_events(
                    events, lane.guard,
                    min_tick=max(sess.max_fed_tick, 0),
                    what=f"session {sess.sid} feed",
                )
            except GuardError:
                lane.rejected += 1
                raise
            backlog = len(sess.sp_tick) - sess.sp_ptr
            incoming = int(np.count_nonzero(events >> 24 == 0x03))
            if backlog + incoming > lane.guard.max_pending_events:
                lane.rejected += 1
                raise QuotaExceededError(
                    f"session {sess.sid}: {backlog} buffered + {incoming} "
                    f"incoming spikes exceeds max_pending_events="
                    f"{lane.guard.max_pending_events}"
                )
        n = sess.feed(events)
        if sess.processable() > 0:
            t0 = self._clock()
            stalled = False
            while not lane.packer.enqueue(sess):
                # Bounded ready-queue full: drain a tile inline to make
                # room.  The stall is admission wait — caller backpressure,
                # not device time — and is excluded from throughput stats.
                stalled = True
                if not self._pump_lane_once(lane):
                    break
            if stalled:
                lane.admission_wait_s += self._clock() - t0
        return n

    def _launch_chunks(self, lane: _ModelLane, sessions, chunks, num_ticks):
        """The shared streaming launch: seat sessions in the pool (one
        batched admission scatter), decode their chunks into one rectangular
        tick-tile, gather carries → ``step_sessions`` → scatter carries.
        Returns the backend's output state (device values, not synced)."""
        self._inject_fault(lane, "stream")
        cfg = lane.cfg
        b_pad = batching.padded_batch_size(len(sessions), lane.max_batch)
        raster, live, valid = batching.decode_session_chunks(
            chunks, cfg.n_in, num_ticks, cfg.label_delay, b_pad=b_pad,
        )
        slots, admit = lane.pool.place(sessions)
        if admit is not None:
            lane.pool.admit(admit)
        idx = lane.pool.padded_slots(slots, b_pad)
        state = lane.pool.gather(idx)
        out = lane.backend.step_sessions(
            lane.weights, jnp.asarray(raster), jnp.asarray(live),
            jnp.asarray(valid), state,
        )
        lane.pool.scatter(idx, out)
        lane.account_tile_bytes(
            num_ticks, b_pad, traffic.stream_step_tiled_bytes
        )
        lane.tiles += 1
        lane.lanes += len(sessions)
        lane.ticks += sum(c.n_live for c in chunks)
        lane.events += sum(len(c.sp_tick) for c in chunks)
        return out

    def _pump_lane_once(self, lane: _ModelLane) -> bool:
        """Pack and launch one streaming tick-tile from one model's lane;
        False when none of its sessions has processable ticks.

        Deadlines are enforced here — *pack time*, before any launch pays
        for the work: an expired session is dropped with a terminal
        EXPIRED snapshot and never occupies a tile lane.  A launch fault
        (device error or injected) rewinds every chosen session's chunk,
        restarts the lane, and re-queues the survivors; a session that
        faults more than ``max_tile_retries`` times in a row is
        quarantined."""
        nxt = lane.packer.next_tile()
        if nxt is None:
            return False
        sessions, num_ticks = nxt
        now = self._clock()
        live = []
        for s in sessions:
            if s.deadline is not None and now > s.deadline:
                self._expire_session(lane, s)
            else:
                live.append(s)
        if not live:
            return True   # handled (dropped) work — the pump made progress
        sessions = live
        chunks = [s.take_chunk(num_ticks) for s in sessions]
        try:
            out = self._launch_chunks(lane, sessions, chunks, num_ticks)
        except CompileError:
            raise   # a program error, not a device fault: never retried
        except Exception:
            self._on_stream_launch_fault(lane, sessions, chunks)
            return True
        self._stream_pending.append(_PendingStreamTile(
            acc_y=out["acc_y"],
            lanes=[(s, s.cursor, s.n_events) for s in sessions],
            t_launch=self._clock(),
            num_ticks=num_ticks,
            lane=lane,
        ))
        for s in sessions:
            if s.processable() > 0:
                lane.packer.enqueue(s)
        self._harvest_stream(block=False)
        while len(self._stream_pending) > self.max_inflight_tiles:
            self._harvest_one()   # backpressure: block on the oldest tile
        return True

    def _on_stream_launch_fault(self, lane, sessions, chunks) -> None:
        """Contain one failed streaming launch: rewind every session's
        chunk (bit-exact — the pool was never scattered into), restart the
        lane, re-queue survivors, quarantine repeat offenders."""
        for s, ref in zip(sessions, chunks):
            s.restore_chunk(ref)
            s.retries += 1
        survivors = [
            s for s in sessions if s.retries <= self._max_tile_retries
        ]
        for s in sessions:
            if s.retries > self._max_tile_retries:
                self._quarantine(lane, s)
        self._restart_lane(lane)
        for s in survivors:
            if s.processable() > 0:
                lane.packer.enqueue(s)

    def _pump_once(self) -> bool:
        """One interleaving round: launch at most one tick-tile per model
        lane (fair share across models — no lane starves behind another's
        backlog); False when no session anywhere has processable ticks."""
        launched = False
        for lane in list(self._lanes.values()):
            launched |= self._pump_lane_once(lane)
        return launched

    def pump(self, drain: bool = False) -> int:
        """Advance every open session through its pending ticks (continuous
        batching: tiles launch asynchronously, harvested opportunistically;
        with several models registered, launches interleave across their
        lanes round-robin).  ``drain`` additionally blocks until all
        launched tiles are harvested.  Returns the number of interleaving
        rounds that launched work."""
        n = 0
        while self._pump_once():
            n += 1
        for lane in self._lanes.values():
            lane.pool.sweep()
        if drain:
            self._harvest_stream(block=True)
        return n

    def _harvest_one(self) -> None:
        p = self._stream_pending.pop(0)
        lane = p.lane
        try:
            acc = np.asarray(p.acc_y)   # synchronises on this tile
        except Exception:
            # Async device fault surfacing at materialisation: every
            # session in this tile ran through the faulted op, and the
            # pool's scatter chain is poisoned behind it — quarantine the
            # tile and restart the lane (other residents are evicted
            # best-effort inside the restart).
            for sess, _, _ in p.lanes:
                self._quarantine(lane, sess)
            if not self._in_restart:
                self._restart_lane(lane)
            return
        lane.tile_lat.append(self._clock() - p.t_launch)
        n = len(p.lanes)
        bad, sat = bad_rows(
            acc[:n], quant=lane.backend.quant,
            ticks=np.array([t for _, t, _ in p.lanes], np.int64),
        )
        lane.saturation_storms += int(sat.sum())
        for i, (sess, ticks, events) in enumerate(p.lanes):
            if sess.status is not ServeStatus.OK:
                continue   # terminal snapshot already written
            if bad[i]:
                # One poisoned sample: quarantine it; its tile-mates'
                # results are delivered below, bitwise untouched (each
                # lane of the tile is an independent carry row).
                self._quarantine(lane, sess)
                continue
            sess.retries = 0
            sess.snapshot = SessionSnapshot(
                sid=sess.sid, pred=int(np.argmax(acc[i])), logits=acc[i],
                label=sess.label, ticks=ticks, events=events,
            )

    def _harvest_stream(self, block: bool) -> None:
        while self._stream_pending and (block or self._stream_pending[0].ready()):
            self._harvest_one()

    def _session_acc(self, sess: _Session) -> np.ndarray:
        """A session's accumulated readout wherever it lives: pool row,
        offloaded host copy, or zeros for a never-run session.  Pool state
        chains on every launched tile, so this is exact without waiting for
        the harvest loop."""
        lane = self._lanes[sess.model_id]
        if sess.slot is not None:
            return np.asarray(lane.pool.state["acc_y"][sess.slot])
        if sess.offloaded is not None:
            return np.asarray(sess.offloaded["acc_y"], np.float32)
        return np.zeros((lane.cfg.n_out,), np.float32)

    def _finish_session(self, sess: _Session) -> SessionSnapshot:
        lane = self._lanes[sess.model_id]
        if sess.status is not ServeStatus.OK:
            # Quarantined/expired mid-stream: the terminal snapshot was
            # already written; result() just hands it over.
            self._sessions.pop(sess.sid, None)
            return sess.snapshot
        sess.closed = True   # extends the horizon to the last fed tick
        if sess.processable() > 0:
            while not lane.packer.enqueue(sess):
                if not self._pump_lane_once(lane):
                    break
        while (sess.status is ServeStatus.OK and sess.processable() > 0
               and self._pump_once()):
            pass
        self._harvest_stream(block=True)
        if sess.status is not ServeStatus.OK:
            self._sessions.pop(sess.sid, None)
            return sess.snapshot
        acc = self._session_acc(sess)
        snap = SessionSnapshot(
            sid=sess.sid, pred=int(np.argmax(acc)), logits=acc,
            label=sess.label, ticks=sess.cursor, events=sess.n_events,
            final=True,
        )
        sess.snapshot = snap
        lane.pool.release(sess)
        self._sessions.pop(sess.sid, None)
        return snap

    def _abandon_session(self, sess: _Session) -> None:
        sess.closed = True
        self._lanes[sess.model_id].pool.release(sess)
        self._sessions.pop(sess.sid, None)

    def reset_stream_stats(self) -> None:
        """Zero the streaming counters of every lane (start of a
        measurement window)."""
        for lane in self._lanes.values():
            lane.reset_counters()

    def _lane_stream_stats(self, lane: _ModelLane, wall_s: float) -> StreamStats:
        lat = np.array(lane.tile_lat) if lane.tile_lat else np.zeros(1)
        tiles = lane.tiles
        sessions = sum(
            1 for s in self._sessions.values() if s.model_id == lane.model_id
        )
        # Throughput over *device* time: callers blocked on a full bounded
        # queue (admission wait) are backpressure, not serving work.
        busy = max(wall_s - lane.admission_wait_s, 1e-9)
        return StreamStats(
            sessions=sessions,
            tiles=tiles,
            events=lane.events,
            ticks=lane.ticks,
            wall_s=wall_s,
            events_per_sec=(
                lane.events / busy if wall_s > 0 else float("inf")
            ),
            ticks_per_sec=(
                lane.ticks / busy if wall_s > 0 else float("inf")
            ),
            p50_tile_latency_s=float(np.percentile(lat, 50)),
            p99_tile_latency_s=float(np.percentile(lat, 99)),
            mean_lanes=(lane.lanes / tiles) if tiles else 0.0,
            evictions=lane.pool.evictions,
            readmissions=lane.pool.readmissions,
            compiled_shapes=lane.backend.compiled_shapes("step_sessions"),
            hbm_bytes_streamed=lane.bytes_streamed,
            rejected=lane.rejected,
            expired=lane.expired,
            shed=lane.shed,
            quarantined=lane.quarantined,
            lane_restarts=lane.lane_restarts,
            saturation_storms=lane.saturation_storms,
            admission_wait_s=lane.admission_wait_s,
        )

    def _compiled_step_shapes(self) -> int:
        """Distinct ``step_sessions`` programs across the engine's lanes,
        counting each pooled backend once (same-bucket models share one jit
        cache, and its shapes must not be double-counted)."""
        uniq = {id(l.backend): l.backend for l in self._lanes.values()}
        return sum(
            be.compiled_shapes("step_sessions") for be in uniq.values()
        )

    def stream_stats(
        self, wall_s: float, model_id: Optional[str] = None
    ) -> StreamStats:
        """Streaming counters since the last :meth:`reset_stream_stats`,
        normalised over the caller-measured wall window.  ``model_id``
        selects one lane; otherwise counters aggregate across lanes, with
        the per-lane breakdown attached as ``per_model`` when the engine
        serves several models."""
        if model_id is not None:
            return self._lane_stream_stats(self._lane(model_id), wall_s)
        lanes = list(self._lanes.values())
        per = {l.model_id: self._lane_stream_stats(l, wall_s) for l in lanes}
        lat = [t for l in lanes for t in l.tile_lat]
        arr = np.array(lat) if lat else np.zeros(1)
        tiles = sum(l.tiles for l in lanes)
        events = sum(l.events for l in lanes)
        ticks = sum(l.ticks for l in lanes)
        wait = sum(l.admission_wait_s for l in lanes)
        busy = max(wall_s - wait, 1e-9)
        return StreamStats(
            sessions=len(self._sessions),
            tiles=tiles,
            events=events,
            ticks=ticks,
            wall_s=wall_s,
            events_per_sec=events / busy if wall_s > 0 else float("inf"),
            ticks_per_sec=ticks / busy if wall_s > 0 else float("inf"),
            p50_tile_latency_s=float(np.percentile(arr, 50)),
            p99_tile_latency_s=float(np.percentile(arr, 99)),
            mean_lanes=(sum(l.lanes for l in lanes) / tiles) if tiles else 0.0,
            evictions=sum(l.pool.evictions for l in lanes),
            readmissions=sum(l.pool.readmissions for l in lanes),
            compiled_shapes=self._compiled_step_shapes(),
            hbm_bytes_streamed=sum(l.bytes_streamed for l in lanes),
            rejected=sum(l.rejected for l in lanes),
            expired=sum(l.expired for l in lanes),
            shed=sum(l.shed for l in lanes),
            quarantined=sum(l.quarantined for l in lanes),
            lane_restarts=sum(l.lane_restarts for l in lanes),
            saturation_storms=sum(l.saturation_storms for l in lanes),
            admission_wait_s=wait,
            per_model=per if len(lanes) > 1 else None,
        )

    # ----------------------------------------- whole-sample compat wrapper

    def _launch_session_tile(
        self, lane: _ModelLane, tile: BatchTile
    ) -> _PendingTile:
        """One whole-sample bucket tile executed through the session-step
        op as a single open-feed-close chunk, with
        :func:`~repro.serve.batching.decode_events_host` semantics exactly:
        the full bucketed tick length runs live (padding ticks advance
        dynamics like the old path) and an END-less buffer pins
        ``end_tick = 0``.

        Each request is a complete stream, so the tile is *stateless* —
        zero carries in (one cached pytree per tile width), carries out
        unobserved — and skips the session pool entirely: whole-sample
        serving pays no pool-sized scatter and no per-request host
        bookkeeping."""
        self._inject_fault(lane, "tile")
        cfg = lane.cfg
        T = tile.num_ticks
        bufs = [req.events for req in tile.requests]
        b_pad = batching.padded_batch_size(len(bufs), lane.max_batch)
        raster, valid, labels = batching.decode_events_host(
            bufs, cfg.n_in, T, cfg.label_delay
        )
        raster, valid = batching.pad_batch(raster, valid, b_pad)
        live = np.zeros((T, b_pad), np.float32)
        live[:, : len(bufs)] = 1.0
        out = lane.backend.step_sessions(
            lane.weights, jnp.asarray(raster), jnp.asarray(live),
            jnp.asarray(valid), lane.zero_state(b_pad),
        )
        lane.account_tile_bytes(T, b_pad, traffic.stream_step_tiled_bytes)
        lane.tiles += 1
        lane.lanes += len(bufs)
        lane.ticks += T * len(bufs)
        return _PendingTile(
            acc_y=out["acc_y"], labels=labels, tile=tile,
            b_live=len(bufs), lane=lane,
        )

    def serve(
        self,
        stream: Iterable[Union[np.ndarray, Tuple[np.ndarray, str]]],
        flush: bool = True,
        model_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
    ) -> Tuple[List[ServeResult], ServeStats]:
        """Run a whole stream of AER sample buffers; results in admission
        (rid) order plus throughput/latency stats.

        Stream items are raw event buffers (routed to ``model_id``, default
        route when ``None``) or ``(events, model_id)`` pairs — mixed-model
        traffic interleaves freely; each buffer lands in its own model's
        scheduler and tiles stay single-model.  Per-model stats ride in
        ``stats.per_model`` whenever more than one model served.

        This is the whole-sample *compatibility wrapper* over the session
        runtime: each bucketed tile (same
        :class:`~repro.serve.scheduler.BucketingScheduler` determinism
        contract as ever) is executed open-feed-close through the session
        machinery — per-request sessions seated in the pool, one
        ``step_sessions`` launch, slots released — producing identical
        results to the historical whole-sample path.  Tiles are *launched*
        as soon as a bucket fills but the host never blocks on them
        mid-stream: results are harvested opportunistically as their device
        buffers become ready and the one mandatory synchronisation happens
        at the end-of-stream drain.  ``flush`` drains the partial buckets
        at end-of-stream.

        Robustness semantics: per-item failures never abort the stream.  A
        buffer the guard rejects, a submit refused by a full bounded
        queue, a shed or deadline-expired request, and a faulted tile all
        surface as results with the corresponding non-OK
        :class:`~repro.serve.guard.ServeStatus` — one misbehaving item
        costs exactly one REJECTED result while its neighbours serve
        unaffected.  ``deadline_s`` stamps a per-item relative deadline
        (falling back to the engine's ``default_deadline_s``).
        """
        t0 = self._clock()
        bytes0 = {
            mid: lane.bytes_streamed for mid, lane in self._lanes.items()
        }
        restarts0 = {
            mid: lane.lane_restarts for mid, lane in self._lanes.items()
        }
        shed0 = {mid: lane.shed for mid, lane in self._lanes.items()}
        results: List[ServeResult] = []
        pending: List[_PendingTile] = []
        batches = 0
        batches_by: Dict[str, int] = {}
        touched: Dict[str, _ModelLane] = {}

        def launch(lane: _ModelLane, tile: BatchTile) -> None:
            """Launch with a fault budget: a launch that raises restarts
            the lane and retries; an exhausted budget FAULTs the tile's
            requests instead of killing the stream."""
            nonlocal batches
            for _ in range(self._max_tile_retries + 1):
                try:
                    pending.append(self._launch_session_tile(lane, tile))
                except CompileError:
                    raise   # a program error, not a device fault
                except Exception:
                    if not self._in_restart:
                        self._restart_lane(lane)
                    continue
                batches += 1
                batches_by[lane.model_id] = (
                    batches_by.get(lane.model_id, 0) + 1
                )
                return
            lane.quarantined += len(tile.requests)
            results.extend(
                self._dead_result(lane, req, ServeStatus.FAULT)
                for req in tile.requests
            )

        def harvest(block: bool) -> None:
            while pending and (block or pending[0].ready()):
                results.extend(self._finalize(pending.pop(0)))

        def reap(lane: _ModelLane) -> None:
            """Shed + deadline-expired requests become results, *before*
            tiles pack — expired work never occupies a launch slot."""
            self._collect_dropped(lane)
            results.extend(lane.dead)
            lane.dead.clear()

        for item in stream:
            if isinstance(item, tuple):
                events, mid = item
            else:
                events, mid = item, model_id
            lane = self._lane(mid)
            touched[lane.model_id] = lane
            try:
                ev = self._validate_for(lane, events)
                lane.scheduler.submit(
                    ev, deadline=self._deadline(deadline_s)
                )
            except (GuardError, OverloadError):
                lane.rejected += 1
                results.append(self._dead_result(
                    lane,
                    ServeRequest(
                        rid=self._alloc_rid(),
                        events=np.zeros(0, np.uint32),
                        native_ticks=0, bucket=0, t_submit=self._clock(),
                    ),
                    ServeStatus.REJECTED,
                ))
            reap(lane)
            for tile in lane.scheduler.ready_tiles():
                launch(lane, tile)
            harvest(block=False)
            while len(pending) > self.max_inflight_tiles:
                # backpressure: the device fell behind — block on the oldest
                # tile so in-flight buffers stay bounded
                results.extend(self._finalize(pending.pop(0)))
        if flush:
            for lane in touched.values():
                reap(lane)
                for tile in lane.scheduler.drain():
                    launch(lane, tile)
        harvest(block=True)   # the single per-drain sync
        wall = self._clock() - t0
        results.sort(key=lambda r: r.rid)

        def lane_bytes(lane: _ModelLane) -> int:
            return lane.bytes_streamed - bytes0.get(lane.model_id, 0)

        def lane_restarts(lane: _ModelLane) -> int:
            return lane.lane_restarts - restarts0.get(lane.model_id, 0)

        def lane_shed(lane: _ModelLane) -> int:
            return lane.shed - shed0.get(lane.model_id, 0)

        stats = ServeStats.collect(
            results, wall, batches, self._compiled_step_shapes(),
            hbm_bytes=sum(lane_bytes(l) for l in self._lanes.values()),
            shed=sum(lane_shed(l) for l in touched.values()),
            lane_restarts=sum(lane_restarts(l) for l in touched.values()),
        )
        if len(touched) > 1:
            stats.per_model = {
                mid: ServeStats.collect(
                    [r for r in results if r.model_id == mid],
                    wall,
                    batches_by.get(mid, 0),
                    lane.backend.compiled_shapes("step_sessions"),
                    hbm_bytes=lane_bytes(lane),
                    shed=lane_shed(lane),
                    lane_restarts=lane_restarts(lane),
                )
                for mid, lane in touched.items()
            }
        return results, stats

    def warmup(
        self,
        num_ticks: int,
        batch: Optional[int] = None,
        model_id: Optional[str] = None,
    ) -> None:
        """Pre-compile the forward programs for one tile shape
        (excluded-from-bench compile time; also useful before
        latency-sensitive serving).  Warms both the session-step program
        (the ``serve()``/streaming path) and the whole-sample inference
        program (the direct ``run_tile`` path)."""
        lane = self._lane(model_id)
        b = batching.padded_batch_size(batch or lane.max_batch, lane.max_batch)
        t = batching.bucket_ticks(num_ticks, self.tick_granularity)
        raster = jnp.zeros((t, b, lane.cfg.n_in), jnp.float32)
        valid = jnp.ones((t, b), jnp.float32)
        jax.block_until_ready(
            lane.backend.inference(lane.weights, raster, valid)["acc_y"]
        )
        state = lane.backend.init_session_state(b)
        jax.block_until_ready(
            lane.backend.step_sessions(
                lane.weights, raster, valid, valid, state
            )["acc_y"]
        )
