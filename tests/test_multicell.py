"""launch/multicell.py end-to-end over a tiny FunctionEvaluator matrix:
a cold run through a Study directory, then a --study re-run that must perform
ZERO fresh evaluations and land on identical incumbents per cell."""
import threading

import pytest

from repro.core import Study
from repro.launch.multicell import cell_platform, tune_cells

CELLS = ["llama3.2-1b:train_4k", "llama3.2-1b:decode_32k"]


class CountingCellEvaluator:
    """Deterministic per-cell objective (cell-dependent optimum) that counts
    fresh evaluator invocations thread-safely."""

    def __init__(self, arch, shape, platform):
        # distinct optima per cell so cross-cell cache collisions would show
        self.target = 8 if shape == "train_4k" else 16
        self.base = 5.0 if platform == "train" else 3.0
        self.calls = 0
        self._lock = threading.Lock()

    def __call__(self, config):
        with self._lock:
            self.calls += 1
        return self.base + abs(config["mesh_model_parallel"] - self.target) * 0.25, {}


def _factory(counters):
    # our evaluator already returns (time, info) tuples, so hand the instance
    # to the scheduler directly instead of wrapping it in FunctionEvaluator
    def factory(arch, shape, space, platform):
        ev = CountingCellEvaluator(arch, shape, platform)
        counters[f"{arch}:{shape}"] = ev
        return ev

    return factory


def test_multicell_cold_then_study_rerun_is_free(tmp_path):
    study_dir = tmp_path / "study"

    # cold run: every trial is fresh
    cold_counters = {}
    with Study.open(study_dir) as study:
        cold = tune_cells(
            CELLS, algorithm="gsft", study=study,
            evaluator_factory=_factory(cold_counters), samples_per_param=2,
        )
    assert set(cold) == set(CELLS)
    for cell in CELLS:
        assert cold_counters[cell].calls > 0
        assert cold[cell].cache_stats["fresh"] == cold_counters[cell].calls

    # --study re-run: zero fresh evaluations, identical incumbents per cell
    warm_counters = {}
    with Study.open(study_dir) as study:
        warm = tune_cells(
            CELLS, algorithm="gsft", study=study,
            evaluator_factory=_factory(warm_counters), samples_per_param=2,
        )
    for cell in CELLS:
        assert warm_counters[cell].calls == 0, cell
        assert warm[cell].cache_stats["fresh"] == 0
        assert warm[cell].cache_stats["cache_hits"] > 0
        assert warm[cell].best_config == cold[cell].best_config
        assert warm[cell].best_time == cold[cell].best_time


def test_multicell_cells_do_not_collide_in_shared_cache(tmp_path):
    """Same knob dicts, different cells: per-cell platform namespacing must
    keep their records (and incumbents) apart."""
    counters = {}
    with Study.open(tmp_path / "study") as study:
        out = tune_cells(
            CELLS, algorithm="gsft", study=study,
            evaluator_factory=_factory(counters), samples_per_param=2,
        )
    train_cell, decode_cell = CELLS
    assert out[train_cell].platform == "train/llama3.2-1b:train_4k"
    assert out[decode_cell].platform == "serve/llama3.2-1b:decode_32k"
    # distinct per-cell objectives => distinct best times (no cache bleed)
    assert out[train_cell].best_time != out[decode_cell].best_time


def test_multicell_second_algorithm_pass_reuses_cells(tmp_path):
    """A second tune_cells pass over the same open study (the warm-start
    workflow) must reuse the cell handles, not rebuild evaluators or trip
    the cell-conflict guard."""
    counters = {}
    with Study.open(tmp_path / "study") as study:
        first = tune_cells(CELLS, algorithm="gsft", study=study,
                           evaluator_factory=_factory(counters),
                           samples_per_param=2)
        calls_after_first = {c: counters[c].calls for c in CELLS}
        second = tune_cells(CELLS, algorithm="crs", study=study,
                            evaluator_factory=_factory({}),  # must NOT be used
                            m=4, k=2, max_rounds=1, seed=0)
    for cell in CELLS:
        # the first pass's evaluator served both sessions (shared scheduler)
        assert counters[cell].calls > calls_after_first[cell]
        assert second[cell].algorithm == "crs"
        assert first[cell].platform == second[cell].platform


def test_multicell_duplicate_cells_in_one_invocation(tmp_path):
    counters = {}
    with Study.open(tmp_path / "study") as study:
        out = tune_cells([CELLS[0], CELLS[0]], algorithm="gsft", study=study,
                         evaluator_factory=_factory(counters),
                         samples_per_param=2)
    assert set(out) == {CELLS[0]}  # second entry replays the same sessions


def test_multicell_rejects_malformed_cells(tmp_path):
    with Study.create(tmp_path / "study") as study:
        with pytest.raises(SystemExit, match="expected ARCH:SHAPE"):
            tune_cells(["llama3.2-1b"], study=study)
        with pytest.raises(SystemExit, match="unknown shape"):
            tune_cells(["llama3.2-1b:bogus_shape"], study=study)


def test_cell_platform_maps_shape_kind():
    assert cell_platform("train_4k") == "train"
    assert cell_platform("decode_32k") == "serve"


def test_roofline_platform_key_namespaces_topology():
    """Runs against a non-default chip count must not share cache records
    with the default topology's."""
    from repro.launch.tune import roofline_platform_key

    default = roofline_platform_key("train", "qwen2-72b", "train_4k", 256)
    other = roofline_platform_key("train", "qwen2-72b", "train_4k", 512)
    assert default == "train/qwen2-72b:train_4k"
    assert other != default and "512" in other
