"""Device time of the adaptive fused train kernel (rsnn_train_alif) per
END_B commit (ms)."""

from bench.readers import per

ALIF_KERNEL = ("rsnn_train_alif",)


def read(run):
    s = run.summary
    kernel_s = s.op_seconds(*ALIF_KERNEL) if s is not None else 0.0
    if kernel_s <= 0:
        return None
    return per(1e3 * kernel_s, run.stats["commits"])
