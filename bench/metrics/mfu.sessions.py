"""Dense-datapath operations of the served session-ticks per second over the chip's int8 peak (%)."""

from bench.readers import session_mfu as read  # noqa: F401
