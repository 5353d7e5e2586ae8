"""Self time of the learner's commit span (repro.learn.commit:
OnlineLearner.train_batch until it returns) per END_B commit (ms)."""

from bench.spans import self_per


def read(run):
    return self_per(run, "repro.learn.commit", "commits", 1e3)
