"""Decode model FLOPs over the device busy time inside the decode-step
spans and the chip's bf16 peak, in %."""


def read(r):
    busy = r.trace.busy_in("decode_step")
    if not busy or not r.counts.get("decode_flops"):
        return None
    return 100.0 * r.counts["decode_flops"] / busy / r.peak["bf16_flops_per_s"]
