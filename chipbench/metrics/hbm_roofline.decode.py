"""Bytes the decode steps need (weights, head, valid KV prefix, new KV
row) over the device busy time inside the decode-step spans and the chip's
HBM bandwidth, in %."""


def read(r):
    busy = r.trace.busy_in("decode_step")
    if not busy or not r.counts.get("decode_bytes"):
        return None
    return 100.0 * r.counts["decode_bytes"] / busy / r.peak["hbm_bytes_per_s"]
