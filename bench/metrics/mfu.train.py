"""Forward and e-prop operations of the committed samples per second over the chip's int8 peak (%)."""

from bench.readers import train_mfu as read  # noqa: F401
