"""Host milliseconds per step blocked on the input pipeline (the
next_batch spans)."""


def read(r):
    n = r.trace.count("next_batch")
    return 1e3 * r.trace.host_in("next_batch") / n if n else None
