"""Device time of the session pool's gather and scatter programs per tile (ms)."""

from bench.readers import pool_ms_per_tile as read  # noqa: F401
