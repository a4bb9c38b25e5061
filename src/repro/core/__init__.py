"""The paper's contribution: auto-tuning of platform configuration parameters.

  - ``space``      — the curated 12-train / 11-serve knob tables (§III)
  - ``scheduler``  — TrialScheduler: batched/cached/pruned trial execution
                     (grown from the paper's CMPE, §VII), plus the trial-log
                     readers
  - ``executors``  — trial isolation backends: inline threads (soft
                     timeouts) / subprocess workers (hard SIGKILL deadlines)
  - ``strategies`` — ask/tell Strategy engine: gsft (Algorithm I, Grid
                     Search with Finer Tuning, §VIII), crs (Algorithm II,
                     Controlled Random Search, §IX), hillclimb, tpe, random,
                     asha
  - ``study``      — Study: persistent, resumable tuning sessions + EngineConfig;
                     the one way a tuning session is run and stored
  - ``transfer``   — cross-cell transfer: sibling histories, cell similarity,
                     config snapping (the ``--transfer off|warm|prior`` modes)
  - ``surrogate``  — learned cost model over the study cache: ridge
                     regression that pre-ranks TPE acquisition candidates
                     (the ``--surrogate off|rank`` modes)
  - ``evaluators`` — walltime (paper-faithful) / roofline (AOT) backends
  - ``roofline``   — TPU v5e roofline terms from compiled artifacts
  - ``hlo``        — collective-traffic parser over partitioned HLO
"""
from repro.core.executors import (
    EvaluatorSpec,
    ExecutionBackend,
    InlineBackend,
    SubprocessBackend,
    make_backend,
)
from repro.core.scheduler import (
    Trial,
    TrialScheduler,
    best_from_log,
    config_hash,
    config_key,
    read_log,
)
from repro.core.space import SERVE_SPACE, SPACES, TRAIN_SPACE, TunableSpace
from repro.core.strategies import (
    CRSResult,
    CRSStrategy,
    CuratedHillclimbStrategy,
    GridFinerStrategy,
    GridResult,
    HillclimbResult,
    Move,
    Strategy,
    TPEResult,
    TPEStrategy,
    make_strategy,
    register_strategy,
)
from repro.core.study import EngineConfig, Study, StudyCell, TuneOutcome, run_session
from repro.core.surrogate import SURROGATE_MODES, CostSurrogate
from repro.core.transfer import (
    TRANSFER_MODES,
    CellKey,
    SiblingHistory,
    default_similarity,
    parse_namespace,
    snap_into_space,
)

__all__ = [
    "EngineConfig",
    "Study",
    "StudyCell",
    "run_session",
    "CRSResult",
    "CRSStrategy",
    "CuratedHillclimbStrategy",
    "EvaluatorSpec",
    "ExecutionBackend",
    "GridFinerStrategy",
    "GridResult",
    "HillclimbResult",
    "InlineBackend",
    "SubprocessBackend",
    "Move",
    "SERVE_SPACE",
    "SPACES",
    "Strategy",
    "TPEResult",
    "TPEStrategy",
    "TRAIN_SPACE",
    "Trial",
    "TrialScheduler",
    "TuneOutcome",
    "TunableSpace",
    "TRANSFER_MODES",
    "SURROGATE_MODES",
    "CostSurrogate",
    "CellKey",
    "SiblingHistory",
    "default_similarity",
    "parse_namespace",
    "snap_into_space",
    "best_from_log",
    "config_hash",
    "config_key",
    "make_backend",
    "make_strategy",
    "read_log",
    "register_strategy",
]
