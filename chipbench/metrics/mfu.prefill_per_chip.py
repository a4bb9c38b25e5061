"""Prefill model FLOPs over the device busy time inside the prefill spans
(averaged over the chips) and the bf16 peak of all the chips in the trace,
in %: ``mfu.prefill`` for a model divided over several chips."""


def read(r):
    busy = r.trace.busy_in("prefill")
    if not busy or not r.counts.get("prefill_flops"):
        return None
    return 100.0 * r.counts["prefill_flops"] / busy / (
        len(r.trace.ops) * r.peak["bf16_flops_per_s"])
