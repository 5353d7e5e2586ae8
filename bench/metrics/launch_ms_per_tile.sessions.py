"""Self time of the program's launch span (repro.serve.launch: pool place,
admit and gather, the step_sessions dispatch and the scatter) per tile the
engine launched (ms)."""

from bench.spans import self_per


def read(run):
    return self_per(run, "repro.serve.launch", "tiles", 1e3)
