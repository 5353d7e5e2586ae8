"""Self time of the program's decode span (repro.serve.decode:
batching.decode_session_chunks) per tile the engine launched (ms)."""

from bench.spans import self_per


def read(run):
    return self_per(run, "repro.serve.decode", "tiles", 1e3)
