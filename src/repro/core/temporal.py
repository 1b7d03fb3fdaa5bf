"""Multi-temporal windowed queries (Kepner et al. [14], paper §IV).

The challenge's statistics are defined per traffic window A_t — the released
dataset is 2^30 packets cut into time windows, and the "multi-temporal
analysis of 100,000,000,000 packets" paper the queries come from studies how
the statistics *scale across window sizes*.  In jaxdf terms a window is just
one more group-by key — but it is a *small static* key (``n_windows`` is a
compile-time constant), which the sort-once plan (DESIGN.md §2.3) exploits:
window w's links are exactly the plan's links restricted to the rows that
fall in w, so every per-window statistic derives from the two already-sorted
plans with zero additional sorts.

The per-window traffic matrix A_w is a *values slice over the shared CSR
skeleton* (``core/sparse.csr_from_plan``, DESIGN.md §2.4): masking the sorted
stream to window w and segment-reducing yields A_w's entry values and
pattern on the same row pointers, and every statistic is a CSR reduction.
Windows are visited by a ``lax.scan`` whose body reuses O(capacity)
buffers, so peak memory is **O(nnz)** — independent of ``n_windows``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .plan import SortedEdges, sorted_edges
from .table import Table

__all__ = [
    "window_ids",
    "windowed_queries",
    "windowed_suite_from_plans",
]


def window_ids(ts: jnp.ndarray, window_len: int, t0=None) -> jnp.ndarray:
    """Map timestamps to consecutive window indices (t0 defaults to min ts)."""
    t0 = jnp.min(ts) if t0 is None else t0
    return ((ts - t0) // jnp.asarray(window_len, ts.dtype)).astype(jnp.int32)


def _side_stats_csr(
    plan: SortedEdges, win: jnp.ndarray, n_windows: int
) -> Dict[str, jnp.ndarray]:
    """Per-window stats of one plan side off per-window CSR segments.

    The shared CSR skeleton (rows = leading endpoints, entries = links) is
    built from the plan for free; for each window w, A_w's entry values are
    the w-masked segment sums over that skeleton — a CSR with the same
    pointers and a sliced value/pattern vector.  A ``lax.scan`` walks the
    static window axis so only ONE window's O(capacity) value buffers are
    live at a time.
    """
    cap = plan.capacity
    valid = plan.valid_rows()
    s_win = jnp.where(
        valid, jnp.clip(win[plan.row], 0, n_windows - 1), n_windows
    )
    ones = valid.astype(jnp.int32)
    w_live = jnp.where(valid, plan.w, 0)
    # link -> leading-endpoint row id: the CSR skeleton's entry_rows(),
    # already available on the plan without materialising the CSR buffers
    # (csr_from_plan(plan).entry_rows() computes the identical map)
    link2row = plan.link_to_k0()[:cap]

    def one_window(carry, w):
        in_w = s_win == w
        rows_w = jnp.where(in_w, ones, 0)
        pk_w = jnp.where(in_w, w_live, 0)
        # A_w's entry values on the shared skeleton: per-link row counts
        # (pattern) and packet sums (values) restricted to window w
        link_cnt = jax.ops.segment_sum(rows_w, plan.seg, num_segments=cap + 1)[:cap]
        link_pk = jax.ops.segment_sum(pk_w, plan.seg, num_segments=cap + 1)[:cap]
        # row-level reductions of A_w (per leading endpoint)
        row_cnt = jax.ops.segment_sum(rows_w, plan.k0_seg, num_segments=cap + 1)[:cap]
        row_pk = jax.ops.segment_sum(pk_w, plan.k0_seg, num_segments=cap + 1)[:cap]
        pk_total = jnp.sum(pk_w)
        present = link_cnt > 0
        # |A_w|_0·1 — degrees of the per-window pattern, reduced over rows
        fan = jax.ops.segment_sum(
            present.astype(jnp.int32), link2row, num_segments=cap + 1
        )[:cap]
        return carry, (
            jnp.sum(present).astype(jnp.int32),        # |A_w|_0
            jnp.max(link_pk),                          # max(A_w)
            jnp.sum(row_cnt > 0).astype(jnp.int32),    # |A_w 1|_0 support
            jnp.max(row_pk),                           # max(A_w 1)
            jnp.max(fan),                              # max(|A_w|_0 1)
            pk_total,                                  # 1^T A_w 1
        )

    _, (uniq_links, max_link_pk, n_uniq, max_pk, max_fan, packets) = jax.lax.scan(
        one_window, 0, jnp.arange(n_windows, dtype=jnp.int32)
    )
    return {
        "unique_links": uniq_links,
        "max_link_packets": max_link_pk,
        "n_unique": n_uniq,
        "max_packets": max_pk,
        "max_fanout": max_fan,
        "valid_packets": packets,
    }


def windowed_suite_from_plans(
    plan_src: SortedEdges,
    plan_dst: SortedEdges,
    win: jnp.ndarray,
    n_windows: int,
) -> Dict[str, jnp.ndarray]:
    """All scalar challenge statistics per window, off the shared plan pair,
    by per-window CSR segments (:func:`_side_stats_csr`)."""
    s = _side_stats_csr(plan_src, win, n_windows)
    d = _side_stats_csr(plan_dst, win, n_windows)
    return {
        "valid_packets": s["valid_packets"],
        "unique_links": s["unique_links"],
        "max_link_packets": s["max_link_packets"],
        "n_unique_sources": s["n_unique"],
        "n_unique_destinations": d["n_unique"],
        "max_source_packets": s["max_packets"],
        "max_source_fanout": s["max_fanout"],
        "max_destination_packets": d["max_packets"],
        "max_destination_fanin": d["max_fanout"],
    }


def windowed_queries(
    t: Table,
    window_len: int,
    n_windows: int,
    ts_col: str = "ts",
    t0=None,
    plans: Optional[Tuple[SortedEdges, SortedEdges]] = None,
) -> Dict[str, jnp.ndarray]:
    """All scalar challenge statistics per time window.

    Args:
      t: packet table with ``src``, ``dst``, ``ts`` (+ optional n_packets).
      window_len: window duration in ts units.
      n_windows: static number of windows to emit (extra windows are empty).
      t0: window origin.  Defaults to the column minimum; pass ``t0=0`` when
        ``ts_col`` already holds window ids (the streaming engine's link
        tables may not contain window 0 mid-stream, and the min-derived
        origin would silently shift every window).
      plans: optional pre-built (src-leading, dst-leading) plan pair — the
        challenge ``analyze`` shares the suite-wide pair so the windowed
        statistics cost zero additional sorts.

    Returns a dict of (n_windows,) arrays:
      valid_packets, unique_links, max_link_packets, n_unique_sources,
      n_unique_destinations, max_source_packets, max_source_fanout,
      max_destination_packets, max_destination_fanin.
    """
    w = t["n_packets"] if "n_packets" in t else jnp.ones((t.capacity,), jnp.int32)
    win = jnp.clip(window_ids(t[ts_col], window_len, t0=t0), 0, n_windows - 1)
    if plans is None:
        plans = (
            sorted_edges(t["src"], t["dst"], weights=w, n_valid=t.n_valid),
            sorted_edges(t["dst"], t["src"], weights=w, n_valid=t.n_valid),
        )
    return windowed_suite_from_plans(plans[0], plans[1], win, n_windows)

