"""Device milliseconds of collective operations (``chipbench.collectives``:
all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all, in
their synchronous, asynchronous and fused forms) inside the prefill spans,
averaged over the chips, per prefill."""
from chipbench import collectives


def read(r):
    n = r.trace.count("prefill")
    busy = collectives.busy_in(r.trace, "prefill")
    return 1e3 * busy / n if n and busy else None
