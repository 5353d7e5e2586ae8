"""Session-first AER serving runtime over the shared execution backend.

The serving model is the **session**: an unbounded per-user AER event
stream with persistent recurrent state — the paper's neuromorphic edge
scenario.  ``BatchedEngine.open_session()`` hands out a
:class:`~repro.serve.engine.SessionHandle` (``feed`` / ``poll`` /
``result`` / ``close``); the engine continuously batches whichever sessions
have pending ticks into fixed-shape tick-tiles, with every session's carry
state resident in a device-side :class:`~repro.serve.session.SessionPool`
(LRU + idle-timeout eviction, bit-exact offload/readmit).  The historical
whole-sample entry points (``submit()`` / ``serve()``) remain supported as
a thin open-feed-close wrapper over the same machinery.

Multi-model serving: a :class:`~repro.serve.registry.ModelRegistry` holds
any number of ``model_id``-keyed networks (config + quant contract +
weight-SRAM image, hot-swappable mid-serve) over one shared backend pool;
``BatchedEngine(registry=...)`` serves them all concurrently, routing every
``submit``/``open_session``/``serve`` call by ``model_id`` — the paper's
runtime reprogrammability (one fabric, many SRAM programs) at service
scale.

* :mod:`repro.serve.session`   — device-resident state pool, session records;
* :mod:`repro.serve.batching`  — ragged-stream decode/padding + capacity math;
* :mod:`repro.serve.scheduler` — whole-sample bucketing + continuous packing;
* :mod:`repro.serve.registry`  — model registry: specs, hot-swap, routing;
* :mod:`repro.serve.engine`    — the engine, session handles, stats.

See ``docs/serving.md`` for the session lifecycle and the migration guide
from the whole-sample API, and ``examples/streaming_sessions.py`` /
``examples/serve_braille.py`` for end-to-end demos.

This package re-exports exactly the supported public surface (``__all__``
below); everything else — host decode internals, pending-tile records,
pool plumbing — is implementation detail reachable through the submodules.
"""

from repro.serve.batching import (
    DEFAULT_SESSION_STATE_BUDGET,
    DEFAULT_VMEM_BUDGET,
    KERNEL_SAMPLE_CAP,
    max_batch_for,
    max_sessions_for,
    request_ticks,
)
from repro.serve.engine import (
    BatchedEngine,
    ServeResult,
    ServeStats,
    SessionHandle,
    StreamStats,
)
from repro.serve.guard import (
    GuardConfig,
    GuardError,
    LaneFaultError,
    MalformedEventError,
    OverloadError,
    QuotaExceededError,
    ServeError,
    ServeStatus,
    StreamContractError,
    bad_rows,
    validate_events,
)
from repro.serve.registry import (
    DEFAULT_MODEL,
    SRAM_KEYS,
    ModelRegistry,
    ModelSpec,
    expected_shapes,
)
from repro.serve.scheduler import (
    BatchTile,
    BucketingScheduler,
    ServeRequest,
    StreamPacker,
)
from repro.serve.session import SessionPool, SessionSnapshot

__all__ = [
    # engine + handles
    "BatchedEngine",
    "SessionHandle",
    "ServeResult",
    "ServeStats",
    "StreamStats",
    "SessionSnapshot",
    # model registry (multi-model serving)
    "ModelRegistry",
    "ModelSpec",
    "expected_shapes",
    "DEFAULT_MODEL",
    "SRAM_KEYS",
    # schedulers
    "BucketingScheduler",
    "StreamPacker",
    "BatchTile",
    "ServeRequest",
    # state pool
    "SessionPool",
    # guard layer + error model (hardened serving)
    "GuardConfig",
    "ServeStatus",
    "ServeError",
    "GuardError",
    "MalformedEventError",
    "StreamContractError",
    "QuotaExceededError",
    "OverloadError",
    "LaneFaultError",
    "validate_events",
    "bad_rows",
    # sizing / capacity math
    "max_batch_for",
    "max_sessions_for",
    "request_ticks",
    "DEFAULT_VMEM_BUDGET",
    "DEFAULT_SESSION_STATE_BUDGET",
    "KERNEL_SAMPLE_CAP",
]
