"""Adaptive fused train kernel (rsnn_train_alif) time against the least
time the chip needs for the window's samples (%).  Operations from
bench/cost.py train_ops: the forward and e-prop contractions; the
adaptation's per-neuron element-wise work is not counted."""

from bench import cost
from bench.readers import dims, peak_ops

ALIF_KERNEL = ("rsnn_train_alif",)


def read(run):
    s = run.summary
    kernel_s = s.op_seconds(*ALIF_KERNEL) if s is not None else 0.0
    if kernel_s <= 0:
        return None
    st = run.stats
    least = cost.least_seconds(
        cost.train_ops(dims(run), st["samples"], st["sample_ticks"]),
        cost.train_bytes(dims(run), st["samples"], st["sample_ticks"],
                         st["commits"]),
        peak_ops(run), run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
