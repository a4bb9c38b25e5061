"""Device busy milliseconds of the program's ``sdpa`` scope (attention
scores, softmax and value reduction, with the GQA K/V repeat) inside the
decode-step spans, per step."""


def read(r):
    n = r.trace.count("decode_step")
    busy = r.trace.busy_in_scope("sdpa", "decode_step")
    return 1e3 * busy / n if n and busy else None
