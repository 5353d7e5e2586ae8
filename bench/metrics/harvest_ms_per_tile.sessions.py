"""Self time of the program's harvest span (repro.serve.harvest: the sync on a
launched tile, bad_rows and the snapshots) per tile the engine launched
(ms)."""

from bench.spans import self_per


def read(run):
    return self_per(run, "repro.serve.harvest", "tiles", 1e3)
