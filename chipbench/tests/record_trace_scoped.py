"""Record the small TPU trace that ``trace_scoped.xplane.pb.gz`` holds.

    python3 chipbench/tests/record_trace_scoped.py <out.xplane.pb.gz>

Three units, each a ``cb:unit`` span holding a ``cb:decode_step`` span
around one call of a jitted two-layer ``lax.scan`` whose layer carries the
named scopes ``attn`` (with ``sdpa`` inside) and ``mlp``, then a
``repro:data.queue_wait`` span on the same thread (a 2 ms sleep: the device
is idle in it). A second thread opens ``repro:data.make_batch`` spans (with
an argument, which the reader strips) all the while. Needs a TPU.
"""
from __future__ import annotations

import glob
import gzip
import os
import sys
import tempfile
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import scopes, trace  # noqa: E402
from repro.obs import span as program_span  # noqa: E402


def layer(x, w):
    wq, w1 = w
    with jax.named_scope("attn"):
        q = x @ wq
        with jax.named_scope("sdpa"):
            p = jax.nn.softmax((q @ x.T).astype(jnp.float32) / 16.0, axis=-1)
            x = x + (p.astype(x.dtype) @ x)
    with jax.named_scope("mlp"):
        x = x + jax.nn.relu(x @ w1) @ w1.T
    return x, None


@jax.jit
def decode_step(x, ws):
    return jax.lax.scan(layer, x, ws)[0]


def producer(stop: threading.Event):
    i = 0
    while not stop.is_set():
        with jax.profiler.TraceAnnotation(scopes.PROGRAM_PREFIX + "data.make_batch", step=i):
            time.sleep(0.003)
        time.sleep(0.001)
        i += 1


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, (1024, 256), jnp.bfloat16)
    ws = (0.05 * jax.random.normal(k2, (2, 256, 256), jnp.bfloat16),
          0.05 * jax.random.normal(k3, (2, 256, 1024), jnp.bfloat16))
    decode_step(x, ws).block_until_ready()  # compiled outside the trace
    stop = threading.Event()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        thread = threading.Thread(target=producer, args=(stop,))
        thread.start()
        for _ in range(3):
            with trace.span("unit"):
                with trace.span("decode_step"):
                    decode_step(x, ws).block_until_ready()
                with program_span("data.queue_wait"):
                    time.sleep(0.002)
        stop.set()
        thread.join()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        with open(path, "rb") as f, gzip.open(out, "wb") as g:
            g.write(f.read())
    s = scopes.read_file(out)
    print(f"{out}: {os.path.getsize(out)} bytes; {len(s.ops[0])} device ops, "
          f"scopes {sorted(set(s.scopes[0]))}")
    print("program spans:", s.program_spans)
    print("breakdown:", s.breakdown())
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
