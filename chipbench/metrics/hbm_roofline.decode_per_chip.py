"""Bytes the decode steps need (weights, head, valid KV prefix, new KV
row) over the device busy time inside the decode-step spans (averaged over
the chips) and the HBM bandwidth of all the chips in the trace, in %:
``hbm_roofline.decode`` for a model divided over several chips."""


def read(r):
    busy = r.trace.busy_in("decode_step")
    if not busy or not r.counts.get("decode_bytes"):
        return None
    return 100.0 * r.counts["decode_bytes"] / busy / (
        len(r.trace.ops) * r.peak["hbm_bytes_per_s"])
