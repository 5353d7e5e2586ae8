"""Host time in BatchedEngine.pump per tile the engine launched (ms)."""

from bench.readers import pump_ms_per_tile as read  # noqa: F401
