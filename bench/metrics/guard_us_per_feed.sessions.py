"""Self time of the program's guard span (repro.serve.guard: validate_events and
the spike quota) per SessionHandle.feed call (us)."""

from bench.spans import self_per


def read(run):
    return self_per(run, "repro.serve.guard", "feed_calls", 1e6)
