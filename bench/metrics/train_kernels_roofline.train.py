"""Fused train kernel time against the least time the chip needs for the window's samples (%)."""

from bench.readers import train_roofline as read  # noqa: F401
