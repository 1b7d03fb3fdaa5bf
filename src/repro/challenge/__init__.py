"""repro.challenge — the end-to-end Anonymized Network Sensing workload.

Runs the full paper pipeline (read -> build -> anonymize -> analyze) as
timed phases over one static-shape table, with an optional single-program
fused path, a shard_map scalar path, and a graph phase (BFS, WCC, PageRank,
triangles) chosen by ``ChallengeConfig.analytics``.  CLI:

    PYTHONPATH=src python -m repro.challenge.run --scale 14
"""
from .pipeline import (
    Analytics,
    ChallengeConfig,
    ChallengePhaseTimings,
    ChallengeResults,
    ChallengeRun,
    analyze,
    cross_window_ip_overlap,
    distributed_scalar_queries,
    run_challenge,
)

__all__ = [
    "Analytics",
    "ChallengeConfig",
    "ChallengePhaseTimings",
    "ChallengeResults",
    "ChallengeRun",
    "analyze",
    "cross_window_ip_overlap",
    "distributed_scalar_queries",
    "run_challenge",
]
