"""Seconds of backend compilation in set-up, from jax.monitoring (a
persistent-cache load counts its retrieval time)."""


def read(r):
    return r.compile_s
