"""Prefill model FLOPs over the device busy time inside the prefill spans
and the chip's bf16 peak, in %."""


def read(r):
    busy = r.trace.busy_in("prefill")
    if not busy or not r.counts.get("prefill_flops"):
        return None
    return 100.0 * r.counts["prefill_flops"] / busy / r.peak["bf16_flops_per_s"]
