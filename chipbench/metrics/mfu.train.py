"""Forward and backward model FLOPs of the traced train steps (recompute
not counted) over the traced window and the chip's bf16 peak, in %."""


def read(r):
    work = r.counts.get("train_flops", 0)
    return 100.0 * work / r.trace.window_s / r.peak["bf16_flops_per_s"] if work else None
