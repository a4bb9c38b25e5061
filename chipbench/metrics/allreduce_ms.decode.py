"""Device milliseconds of collective operations (``chipbench.collectives``:
all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all, in
their synchronous, asynchronous and fused forms) inside the decode-step
spans, averaged over the chips, per step."""
from chipbench import collectives


def read(r):
    n = r.trace.count("decode_step")
    busy = collectives.busy_in(r.trace, "decode_step")
    return 1e3 * busy / n if n and busy else None
