"""Drive a cell's whole run on the CPU at a small size: everything but the
look for a chip."""

from __future__ import annotations

import time

from bench import harness

CPU_PEAKS = harness.peaks_for("TPU v5 lite")


def cpu_run(workload: str, seed: int = 1, seconds: float = 0.5,
            trace: int = 0, **traffic) -> harness.Run:
    import jax

    spec = harness.load_spec()
    cell, config, tr = harness.resolve(spec, workload)
    tr = dict(tr, **traffic)
    if "dw_operands" in config:
        # The CPU runs the learner on the scan backend, whose e-prop
        # contractions read float32 operands (HIGHEST), not the TPU
        # kernel's bfloat16: the reference follows the path driven.
        config = dict(config, dw_operands="float32")
    run = harness.Run(cell, config, tr, seed, seconds, trace,
                      jax.devices()[:1], CPU_PEAKS, time.perf_counter())
    harness.driver(tr["driver"]).run(run)
    run.check("compiles_in_window", run.stats["compiles_in_window"], 0)
    return run


SMALL_SESSIONS = dict(sessions=300, character_pool=24, feed_block=150,
                      characters_per_session=4)
