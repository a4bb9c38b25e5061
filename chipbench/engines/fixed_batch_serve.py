"""Closed-loop fixed-batch serving: batch after batch of same-length prompts,
prefilled together and decoded greedily token by token.

The program has no reusable batch-serving entry, so this is a copy of the
loop ``repro.launch.serve._measured_serve`` wires: the program's
``make_prefill_step`` / ``make_decode_step`` bundles, ``.jit()``,
``grow_caches`` to decode capacity, and per token a decode call, a greedy
choice and a wait for the token on the host. Each batch is due when the
previous one finished; its requests' first tokens arrive when the prefill's
greedy choice is on the host.

Weights, prompts and patch stand-ins come from ``chipbench.gen``. After the
window, a sample of finished requests drawn from the seed is run through the
plain reference (``chipbench.reference.dense_lm``) over its prompt and
served tokens; the number compared is the widest gap by which a served
token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, gen, program
from chipbench.reference import dense_lm
from chipbench.trace import span
from repro.configs.base import RunConfig, ShapeConfig
from repro.distributed.steps import make_decode_step, make_prefill_step
from repro.launch.serve import grow_caches


@dataclasses.dataclass
class Record:
    """What the check needs once the device state is gone."""

    served: dict  # request id -> (max_new,) served token ids


class Engine:
    def __init__(self, cell, seed: int, mesh, clock):
        cfg, tr = cell.config, cell.traffic
        self.cfg, self.seed, self.clock = cfg, seed, clock
        self.B, self.P, self.N = tr["batch"], tr["prompt_len"], tr["max_new"]
        self.n_img, self.V = tr["image_tokens"], cfg["vocab_size"]
        arch = program.arch(cfg, self.n_img)
        run = RunConfig(**cfg.get("run", {}))
        pre = make_prefill_step(
            arch, run, ShapeConfig("bench_prefill", self.P, self.B, "prefill"), mesh)
        dec = make_decode_step(
            arch, run, ShapeConfig("bench_decode", self.P + self.N, self.B, "decode"), mesh)
        t = clock()
        self.key = gen.seed_key(seed)
        self.params = jax.block_until_ready(gen.weights(
            self.key, pre.abstract_inputs[0], program.named(mesh, pre.in_shardings[0])))
        self.setup_phases = {"weights_s": clock() - t}
        self.bundles = {"prefill": pre, "decode": dec}
        self.prefill_fn, self.decode_fn = pre.jit(), dec.jit()
        V = self.V  # greedy over the real vocabulary, not the padded rows
        self.greedy = jax.jit(
            lambda logits: jnp.argmax(logits[:, :V], -1)[:, None].astype(jnp.int32))

        self.next_request, self.last_done = 0, None
        self.itl, self.ttft, self.served, self.unit_counts = [], [], {}, []
        self.phases = []  # per unit: host seconds of inputs, prefill, decode
        self.tokens_out = 0
        # warm-up: every program the window runs, once, outside it
        t = clock()
        logits, caches = self.prefill_fn(self.params, self._inputs(np.arange(self.B)))
        tok = self.greedy(logits)
        caches = grow_caches(caches, self.N)
        logits, caches = self.decode_fn(self.params, caches, self._step(tok, 0))
        np.asarray(self.greedy(logits))
        self.setup_phases["warm_up_s"] = clock() - t

    def _inputs(self, rids):
        toks = np.stack([gen.prompt_tokens(self.seed, int(r), self.P, self.n_img, self.V)
                         for r in rids])
        return {"tokens": jnp.asarray(toks),
                "patches": gen.patches(self.key, jnp.asarray(rids, jnp.int32),
                                       self.n_img, self.cfg["hidden_size"])}

    def _step(self, tok, i):
        return {"tokens": tok, "cache_len": jnp.asarray(self.P + i, jnp.int32)}

    def unit(self):
        """One batch, from dispatch to its last token on the host."""
        clock = self.clock
        rids = np.arange(self.next_request, self.next_request + self.B)
        due = self.last_done if self.last_done is not None else clock()
        with span("unit"):
            t_in = clock()
            with span("inputs"):
                batch = self._inputs(rids)
            t_pre = clock()
            with span("prefill"):
                logits, caches = self.prefill_fn(self.params, batch)
                tok = self.greedy(logits)
                out = [np.asarray(tok)]
            t_first = clock()
            with span("grow_caches"):
                caches = grow_caches(caches, self.N)
            for i in range(self.N - 1):
                t = clock()
                with span("decode_step"):
                    logits, caches = self.decode_fn(self.params, caches, self._step(tok, i))
                    with span("argmax_sync"):
                        tok = self.greedy(logits)
                        out.append(np.asarray(tok))
                self.itl.append(clock() - t)
            del caches
        self.last_done = clock()
        steps = self.itl[len(self.itl) - (self.N - 1):] if self.N > 1 else [0.0]
        self.phases.append({"inputs_s": t_pre - t_in, "prefill_s": t_first - t_pre,
                            "decode_s": self.last_done - t_first,
                            "longest_step_s": max(steps)})
        self.ttft += [t_first - due] * self.B
        self.next_request += self.B
        self.tokens_out += self.B * self.N
        served = np.concatenate(out, axis=1)
        for j, r in enumerate(rids):
            self.served[int(r)] = served[j]
        cfg, B, P = self.cfg, self.B, self.P
        self.unit_counts.append({
            "prefills": 1, "prefill_flops": flops.prefill_flops(cfg, B, P),
            "decode_steps": self.N - 1,
            "decode_flops": sum(flops.decode_flops(cfg, B, P + i) for i in range(self.N - 1)),
            "decode_bytes": sum(flops.decode_bytes(cfg, B, P + i) for i in range(self.N - 1)),
        })

    def end_to_end(self, window_s: float) -> dict:
        return {
            "out_tok_s": self.tokens_out / window_s,
            "itl_p95_ms": 1e3 * quantile(self.itl, 0.95),
            "ttft_p90_ms": 1e3 * quantile(self.ttft, 0.90),
        }

    def samples(self) -> dict:
        return {"requests": len(self.served), "decode_steps": len(self.itl),
                "first_tokens": len(self.ttft)}

    def counts(self, units) -> dict:
        return program.sum_counts(self.unit_counts, units)

    def detail(self, unit: int) -> dict:
        return self.phases[unit]

    def attempted(self):
        return len(self.served), 0

    def release(self) -> Record:
        record = Record(served=self.served)
        self.params = self.prefill_fn = self.decode_fn = self.greedy = self.bundles = None
        return record


def quantile(values, q: float) -> float:
    """Linear-interpolation quantile, numpy's default convention (copied from
    ``repro.serving.metrics.quantile``)."""
    if not values:
        raise ValueError("quantile of an empty sample")
    ordered = sorted(values)
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] * (1.0 - pos + lo) + ordered[hi] * (pos - lo)


def check_sample(done, batch: int, n: int, rng) -> list:
    """``n`` finished requests drawn from the seed, spread over the batch
    slots: every slot once before any slot twice, each from a batch not yet
    drawn while one is left, so a fault in one slot or one batch is seen."""
    order = [int(r) for r in rng.permutation(done)]
    picked, slots, units = [], set(), set()
    rules = (lambda r: r % batch not in slots and r // batch not in units,
             lambda r: r % batch not in slots,
             lambda r: True)
    for rule in rules:
        for r in order:
            if len(picked) == n:
                return sorted(picked)
            if r not in picked and rule(r):
                picked.append(r)
                slots.add(r % batch)
                units.add(r // batch)
    return sorted(picked)


def gaps(cell, seed: int, record: Record, control: bool = False) -> dict:
    """Over a seeded sample of finished requests, the widest gap by which the
    reference's logit of a served token lies below its best (``program``).
    With ``control``, also the reference computed in float8 put in the
    program's place: at the same positions, the gap of the token it puts
    first (``control``)."""
    cfg, tr = cell.config, cell.traffic
    P, n_img = tr["prompt_len"], tr["image_tokens"]
    rng = np.random.default_rng((seed, 1))
    sample = check_sample(sorted(record.served), tr["batch"], tr["check_requests"], rng)
    key = gen.seed_key(seed)
    rows = tr["check_rows_per_block"]
    blocks, served = [], []
    for lo in range(0, len(sample), rows):
        rids = sample[lo:lo + rows]
        s = np.stack([record.served[r] for r in rids])
        prompts = np.stack([gen.prompt_tokens(seed, r, P, n_img, cfg["vocab_size"])
                            for r in rids])
        blocks.append((jnp.asarray(np.concatenate([prompts, s[:, :-1]], axis=1)),
                       gen.patches(key, jnp.asarray(rids, jnp.int32), n_img,
                                   cfg["hidden_size"])))
        served.append(jnp.asarray(s))
    ref = dense_lm.DenseLM(cfg, key).logits(blocks, P - 1)
    out = {"program": max(float(jnp.max(dense_lm.served_gaps(l, t)))
                          for l, t in zip(ref, served))}
    if control:
        low = dense_lm.DenseLM(cfg, key, quant=dense_lm.fp8).logits(blocks, P - 1)
        out["control"] = max(float(jnp.max(dense_lm.served_gaps(l, jnp.argmax(q, -1))))
                             for l, q in zip(ref, low))
    return out


def check(cell, seed: int, record: Record, control: bool = False) -> dict:
    """The number compared, beside its limit; with ``control``, the
    control's reading stands in for the program's."""
    name = "served_logit_gap"
    value = gaps(cell, seed, record, control)["control" if control else "program"]
    return {name: {"value": value, "limit": cell.limits[name]}}
