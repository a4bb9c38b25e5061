"""Device busy milliseconds inside the train-step spans, per step."""


def read(r):
    n = r.trace.count("train_step")
    return 1e3 * r.trace.busy_in("train_step") / n if n else None
