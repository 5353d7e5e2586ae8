"""Answered requests per launched tile over the window (ServeStats.mean_batch
of every serve call, pooled): how full the bucket tiles run."""


def read(run):
    return run.stats.get("mean_batch")
