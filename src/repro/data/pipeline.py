"""Deterministic synthetic LM data pipeline with per-host sharding and
background prefetch.

Production shape: each host materializes only its slice of the global batch
(``host_slice``) and assembles a globally-sharded ``jax.Array`` from the
local shards. A prefetch thread synthesizes up to ``prefetch_depth`` host
batches ahead, overlapping that work with the step; the consumer still
waits whenever synthesis is slower than a step, and the copy to the device
(``_to_device``) runs on the consumer's thread, in the step's path. The
host spans ``repro:data.make_batch`` (producer), ``repro:data.queue_wait``
and ``repro:data.to_device`` (consumer) time the three parts in a profiler
trace. The corpus is a seeded zipfian stream, so every run (and every
restart — see ``state_dict``) is bit-exact reproducible; a restart resumes
from the same step's batch.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Any, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.obs import span


@dataclass
class PipelineConfig:
    seed: int = 0
    prefetch_depth: int = 2
    zipf_a: float = 1.2


class SyntheticLMPipeline:
    """Deterministic token stream → sharded train batches."""

    def __init__(self, arch: ArchConfig, shape: ShapeConfig, cfg: PipelineConfig = PipelineConfig(),
                 mesh=None, batch_sharding=None):
        self.arch = arch
        self.shape = shape
        self.cfg = cfg
        self.mesh = mesh
        self.batch_sharding = batch_sharding
        self.step = 0
        self.n_hosts = jax.process_count()
        self.host_id = jax.process_index()

    # ------------------------------------------------------------- batches

    def _host_batch(self, step: int) -> Dict[str, np.ndarray]:
        """The slice of the global batch owned by this host, derived purely
        from (seed, step, host) — no cross-host coordination needed."""
        b, s = self.shape.global_batch, self.shape.seq_len
        per_host = max(b // self.n_hosts, 1)
        rng = np.random.default_rng((self.cfg.seed, step, self.host_id))
        # zipf via inverse-cdf on a fixed rank table (cheap + deterministic)
        u = rng.random((per_host, s + 1))
        ranks = u ** (-1.0 / (self.cfg.zipf_a - 1.0))
        ranks = np.nan_to_num(ranks, posinf=float(self.arch.vocab_size))
        toks = np.minimum(ranks, self.arch.vocab_size - 1).astype(np.int32)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if self.arch.frontend == "vision":
            batch["patches"] = rng.standard_normal(
                (per_host, self.arch.frontend_seq, self.arch.d_model), dtype=np.float32
            ) * 0.02
        elif self.arch.frontend == "audio":
            batch["frames"] = rng.standard_normal(
                (per_host, self.arch.frontend_seq, self.arch.d_model), dtype=np.float32
            ) * 0.02
        return batch

    def _to_device(self, host_batch: Dict[str, np.ndarray]):
        with span("data.to_device"):
            if self.mesh is None or self.batch_sharding is None:
                return {k: jnp.asarray(v) for k, v in host_batch.items()}
            from jax.sharding import NamedSharding

            out = {}
            for k, v in host_batch.items():
                sh = NamedSharding(self.mesh, self.batch_sharding[k])
                out[k] = jax.device_put(v, sh)
            return out

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        q: "queue.Queue" = queue.Queue(maxsize=self.cfg.prefetch_depth)
        stop = threading.Event()

        def producer():
            step = self.step
            while not stop.is_set():
                try:
                    with span("data.make_batch"):
                        host_batch = self._host_batch(step)
                    q.put(host_batch, timeout=0.1)
                    step += 1
                except queue.Full:
                    continue

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                with span("data.queue_wait"):
                    host_batch = q.get()
                self.step += 1
                yield self._to_device(host_batch)
        finally:
            stop.set()

    def take(self, n: int):
        it = iter(self)
        for _ in range(n):
            yield next(it)

    # ------------------------------------------------------------ restarts

    def state_dict(self) -> Dict[str, Any]:
        return {"step": self.step, "seed": self.cfg.seed}

    def load_state_dict(self, state: Dict[str, Any]):
        self.step = int(state["step"])
