"""Device busy milliseconds inside the prefill spans, per prefill."""


def read(r):
    n = r.trace.count("prefill")
    return 1e3 * r.trace.busy_in("prefill") / n if n else None
