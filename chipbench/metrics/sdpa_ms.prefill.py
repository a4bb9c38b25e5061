"""Device busy milliseconds of the program's ``sdpa`` scope (attention
scores, softmax and value reduction, with the GQA K/V repeat) inside the
prefill spans, per prefill."""


def read(r):
    n = r.trace.count("prefill")
    busy = r.trace.busy_in_scope("sdpa", "prefill")
    return 1e3 * busy / n if n and busy else None
