"""Live sessions per launched tile, from the engine's counters (lanes)."""

from bench.readers import per


def read(run):
    return per(run.stats["lanes"], run.stats["tiles"])
