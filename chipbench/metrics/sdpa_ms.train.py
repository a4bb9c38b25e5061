"""Device busy milliseconds of the program's ``sdpa`` scope inside the
train-step spans, per step: encoder, decoder and cross attention, forward,
recompute and backward."""


def read(r):
    n = r.trace.count("train_step")
    busy = r.trace.busy_in_scope("sdpa", "train_step")
    return 1e3 * busy / n if n and busy else None
