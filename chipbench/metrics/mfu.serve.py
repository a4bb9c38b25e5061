"""Model FLOPs of the traced serving units (prefills and decode steps) over
the traced window and the chip's bf16 peak, in %."""


def read(r):
    work = r.counts.get("prefill_flops", 0) + r.counts.get("decode_flops", 0)
    return 100.0 * work / r.trace.window_s / r.peak["bf16_flops_per_s"] if work else None
