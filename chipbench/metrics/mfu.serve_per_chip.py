"""Model FLOPs of the traced serving units (prefills and decode steps) over
the traced window and the bf16 peak of all the chips in the trace, in %:
``mfu.serve`` for a model divided over several chips."""


def read(r):
    work = r.counts.get("prefill_flops", 0) + r.counts.get("decode_flops", 0)
    if not work:
        return None
    return 100.0 * work / r.trace.window_s / (len(r.trace.ops) * r.peak["bf16_flops_per_s"])
