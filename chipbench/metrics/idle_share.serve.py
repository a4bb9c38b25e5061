"""Device idle share of the traced serving units: 1 - busy / window, in %."""


def read(r):
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
