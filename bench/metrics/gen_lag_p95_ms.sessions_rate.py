"""95th percentile of how late the replayed schedule handed each feed to the
engine (ms): a late generator must not read as a fast server."""


def read(run):
    return run.stats.get("gen_lag_p95_ms")
