"""rsnn_step_sessions kernel time against the least time the chip needs for the window's session-ticks (%)."""

from bench.readers import session_roofline as read  # noqa: F401
