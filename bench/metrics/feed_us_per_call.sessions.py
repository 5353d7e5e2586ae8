"""Host time per SessionHandle.feed call, guard and session bookkeeping
included (us)."""

from bench.readers import per


def read(run):
    return per(1e6 * run.stats["feed_s"], run.stats["feed_calls"])
