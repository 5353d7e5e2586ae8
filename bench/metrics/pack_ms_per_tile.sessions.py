"""Self time of the program's pack span (repro.serve.pack:
StreamPacker.next_tile, the deadline filter and take_chunk) per tile the
engine launched (ms)."""

from bench.spans import self_per


def read(run):
    return self_per(run, "repro.serve.pack", "tiles", 1e3)
