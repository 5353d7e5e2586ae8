"""Shared arithmetic of the per-layer metric readers in ``bench/metrics/``.

A reader takes the finished :class:`bench.harness.Run` and returns the
metric's value, or ``None`` where the run holds nothing to read (the harness
then leaves the metric out of the result line).
"""

from __future__ import annotations

from bench import cost

# Kernel names as the device trace shows them.
SESSION_KERNEL = ("rsnn_step_sessions",)
TRAIN_KERNELS = ("rsnn_train", "train_kernel", "train_dma_kernel")
POOL_PROGRAMS = ("_gather", "_scatter")


def dims(run):
    c = run.config
    return c["n_in"], c["n_hid"], c["n_out"]


def peak_ops(run) -> float:
    return run.peaks[run.config["peak"]]


def per(num, den):
    return num / den if den else None


def idle_share(run):
    s = run.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)


def session_roofline(run):
    s = run.summary
    kernel_s = s.op_seconds(*SESSION_KERNEL) if s is not None else 0.0
    if kernel_s <= 0:
        return None
    st = run.stats
    least = cost.least_seconds(
        cost.session_ops(dims(run), st["launched_ticks"]),
        cost.session_bytes(dims(run), st["lanes"], st["events"], st["tiles"]),
        peak_ops(run), run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s


def session_mfu(run):
    ops = cost.session_ops(dims(run), run.stats["session_ticks"])
    return 100.0 * ops / run.window_s / peak_ops(run)


def train_roofline(run):
    s = run.summary
    kernel_s = s.op_seconds(*TRAIN_KERNELS) if s is not None else 0.0
    if kernel_s <= 0:
        return None
    st = run.stats
    least = cost.least_seconds(
        cost.train_ops(dims(run), st["samples"], st["sample_ticks"]),
        cost.train_bytes(dims(run), st["samples"], st["sample_ticks"],
                         st["commits"]),
        peak_ops(run), run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s


def train_mfu(run):
    st = run.stats
    ops = cost.train_ops(dims(run), st["samples"], st["sample_ticks"])
    return 100.0 * ops / run.window_s / peak_ops(run)


def pump_ms_per_tile(run):
    return per(1e3 * run.stats["pump_s"], run.stats["tiles"])


def pool_ms_per_tile(run):
    s = run.summary
    if s is None:
        return None
    pool_s = s.module_seconds(*POOL_PROGRAMS)
    return per(1e3 * pool_s, run.stats["tiles"]) if pool_s > 0 else None
