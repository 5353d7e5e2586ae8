"""Share of the window in which no operation ran on the device (%)."""

from bench.readers import idle_share as read  # noqa: F401
