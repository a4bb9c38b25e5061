import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimbing driver: evaluate a curated, hypothesis-tagged list of
knob changes for one (arch × shape) cell on the production mesh, recording
hypothesis → change → before → after per iteration.

The curated lists are ``Move`` sequences run as a ``hillclimb`` session of
a Study (same path as GSFT/CRS), so a sweep gets the persistent evaluation
cache and per-trial failure handling for free.

    PYTHONPATH=src python -m repro.launch.hillclimb --cell qwen2-72b:train_4k

Without ``--study`` the sessions are stored in ``results/studies/hillclimb``.
"""
import argparse
import json
from pathlib import Path

from repro.core import Study

# (name, hypothesis, overrides) per cell — the napkin math lives in
# EXPERIMENTS.md §Perf next to the measured outcome.
#
# ITERATION 2 lists (results/perf/). Iteration 1 (results/perf/iter1/) ran the
# broad screen and two code-level findings came out of it:
#   (a) seq-parallel residual + head-sharded qkv collided in one PartitionSpec
#       (fixed: shard fn drops duplicate mesh-axis uses), and
#   (b) the MoE dispatch scatter had NO sharding constraints — GSPMD replicated
#       it ("involuntary full rematerialization"), which was the collective
#       bottleneck of the MoE cells (fixed: explicit dispatch shardings).
# ITERATION 3: after the bf16 pre-cast (FSDP weight all-gathers move bf16
# instead of f32 masters — code change in Model._cast_params). Baselines are
# re-measured so the code-level gains are attributed.
CANDIDATES = {
    "qwen2-72b:train_4k": [
        ("baseline", "paper-faithful defaults, now with bf16 weight-gathers (code-level change — expect collective ≈ halved vs iter2 baseline)", {}),
        ("rs_mp8_micro", "iter2 winner re-measured: seqpar + TP=8 + 8 microbatches + bf16 moments", {"collective_matmul": "rs", "mesh_model_parallel": 8, "microbatch_size": 32, "optimizer_moment_dtype": "bfloat16"}),
        ("rs_mp8_micro64", "fewer microbatches (4): fewer weight-gather rounds, bigger live set", {"collective_matmul": "rs", "mesh_model_parallel": 8, "microbatch_size": 64, "optimizer_moment_dtype": "bfloat16"}),
        ("rs_mp8_micro16", "more microbatches (16): more gathers, less memory", {"collective_matmul": "rs", "mesh_model_parallel": 8, "microbatch_size": 16, "optimizer_moment_dtype": "bfloat16"}),
        ("rs_mp4_micro", "TP=4: even smaller activation collectives; kv=8 still divides", {"collective_matmul": "rs", "mesh_model_parallel": 4, "microbatch_size": 32, "optimizer_moment_dtype": "bfloat16"}),
    ],
    "jamba-1.5-large-398b:prefill_32k": [
        ("baseline", "re-measure with bf16 weight-gathers (serve weights were already bf16 — expect ≈ iter2; stop criterion already met)", {}),
        ("rs_final", "seqpar residual (≈ tied in iter2) — final confirmation", {"collective_matmul": "rs"}),
    ],
    "llama4-maverick-400b-a17b:train_4k": [
        ("baseline", "defaults with bf16 weight-gathers", {}),
        ("rs_micro32_bf16m", "iter2 best re-measured", {"collective_matmul": "rs", "microbatch_size": 32, "optimizer_moment_dtype": "bfloat16"}),
        ("rs_micro16_bf16m", "16 microbatches: the last ~4 GiB to get under 16 GiB", {"collective_matmul": "rs", "microbatch_size": 16, "optimizer_moment_dtype": "bfloat16"}),
        ("rs_micro8_bf16m", "32 microbatches — probe the gather-overhead tail", {"collective_matmul": "rs", "microbatch_size": 8, "optimizer_moment_dtype": "bfloat16"}),
    ],
}


def run_cell_sweep(cell: str, out_dir: Path, *, study: Study):
    """Run the cell's curated moves as one ``hillclimb`` session of
    ``study``: the sweep lands in sessions.jsonl (report() rows, resumable
    provenance) and shares the study-wide cache under the cell's namespace.
    A caller that wants another evaluator (a stub for wiring checks) creates
    the cell first with ``study.cell(arch, shape, evaluator=...)``."""
    arch_name, shape_name = cell.split(":")
    res = study.cell(arch_name, shape_name).optimize(
        "hillclimb", moves=CANDIDATES[cell]
    ).detail
    results = res.records
    base = results[0].get("t_step_s", float("nan")) if results else float("nan")
    for rec in results:
        print(f"[{cell}] {rec['name']:16s} t_step={rec.get('t_step_s', float('nan')):8.3f}s "
              f"({rec.get('bottleneck', 'ERR'):10s}) vs baseline {base:8.3f}s "
              f"hbm={rec.get('hbm_est_gib', 0):6.1f}GiB", flush=True)

    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{arch_name}__{shape_name}.json").write_text(
        json.dumps(results, indent=1, default=float))
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=list(CANDIDATES), required=True)
    ap.add_argument("--out", type=Path, default=Path("results/perf"))
    ap.add_argument("--study", type=Path,
                    default=Path("results/studies/hillclimb"),
                    help="Study directory (cache + trial log + session "
                         "provenance; created on first use)")
    args = ap.parse_args()
    with Study.open(args.study) as study:
        run_cell_sweep(args.cell, args.out, study=study)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
