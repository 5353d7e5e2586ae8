"""Host time per END_B commit: pipeline decode and OnlineLearner.train_batch
until the call returns (ms)."""

from bench.readers import per


def read(run):
    return per(1e3 * run.stats["commit_host_s"], run.stats["commits"])
