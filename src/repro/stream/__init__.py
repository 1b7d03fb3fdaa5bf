"""repro.stream — streaming incremental analytics engine (DESIGN.md §6).

Consumes packet micro-batches and maintains mergeable state — a persistent
anonymization dictionary with stable incremental ids, the accumulated
windowed traffic matrix, and per-window activity histograms folded through
the kernels.ops accumulate path — from which all 14 Table III queries are
answerable at any point, identical to a one-shot batch run.  CLI:

    PYTHONPATH=src python -m repro.stream.run --scale 12 --batches 3
"""
from .engine import (
    StreamBatchTimings,
    StreamConfig,
    StreamEngine,
    StreamSnapshot,
    anonymization_mapping,
    link_table,
    merge_states,
    steady_state,
    stream_plq,
    update_state,
)
from .algorithms import snapshot_algorithms
from .recovery import (
    DegradePolicy,
    ServiceReport,
    SimulatedCrash,
    StreamCheckpointer,
    run_service,
)
from .state import StreamState, init_state

__all__ = [
    "DegradePolicy",
    "ServiceReport",
    "SimulatedCrash",
    "StreamBatchTimings",
    "StreamCheckpointer",
    "StreamConfig",
    "StreamEngine",
    "StreamSnapshot",
    "StreamState",
    "anonymization_mapping",
    "init_state",
    "link_table",
    "snapshot_algorithms",
    "merge_states",
    "run_service",
    "steady_state",
    "stream_plq",
    "update_state",
]
