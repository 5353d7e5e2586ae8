"""Self time of the data pipeline's decode span (repro.data.decode:
decode_events_to_batch on an offloaded batch) per END_B commit (ms)."""

from bench.spans import self_per


def read(run):
    return self_per(run, "repro.data.decode", "commits", 1e3)
