"""Device busy milliseconds inside the decode-step spans, per step."""


def read(r):
    n = r.trace.count("decode_step")
    return 1e3 * r.trace.busy_in("decode_step") / n if n else None
