"""The 14 Anonymized-Network-Sensing Graph Challenge queries (paper Table III).

All queries operate on a *packet table* — a :class:`repro.core.table.Table`
with columns ``src``, ``dst`` and (optionally) ``n_packets`` (defaults to 1
per row, i.e. one row per packet as in the raw capture).  The traffic matrix
``A_t`` of the challenge is the group-by of that table on (src, dst) with
packet sums, exactly as the paper's
``df.groupby(by=['src','dst']).value_counts()``.

Each query mirrors one paper Table III row (matrix / summation / data-science
notation reproduced in the docstrings).  Destination-side queries are the
``src``/``dst`` swap per the paper's note.

The whole suite (:func:`run_all_queries`, :func:`scalar_queries_from_plans`)
derives from one shared sort-once plan pair (DESIGN.md §2.3, 3 sorts), and
:func:`table_csrs` reads the same pair as GraphBLAS-lite CSR matrices
(DESIGN.md §2.4) for the graph kernels.  The per-window maxima are CSR
reductions too, in :mod:`repro.core.temporal` — per-window value slices
over the shared CSR skeleton, reduced one window at a time.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .ops import (
    GroupResult,
    UniqueResult,
    argmax_top_k,
    clamp_k,
    groupby_aggregate,
    isin,
    masked_max,
    semi_join,
    top_k,
    unique,
)
from .plan import (
    SortedEdges,
    lead_fanout,
    lead_groups,
    link_groups,
    plan_for_table,
    unique_concat,
)
from .sparse import CsrMatrix, csr_from_plan
from .table import Table

__all__ = [
    "TopLinks",
    "top_links",
    "top_links_from_plan",
    "table_plans",
    "table_csrs",
    "scalar_queries_from_plans",
    "packet_weights",
    "traffic_matrix",
    "valid_packets",
    "unique_links",
    "link_packets",
    "max_link_packets",
    "unique_sources",
    "unique_destinations",
    "unique_ips",
    "packets_per_source",
    "max_source_packets",
    "source_fanout",
    "max_source_fanout",
    "packets_per_destination",
    "max_destination_packets",
    "destination_fanin",
    "max_destination_fanin",
    "QueryResults",
    "run_all_queries",
    "top_k_drift",
    "top_links_drift",
    "new_talker_rate",
    "new_talker_rate_exact",
    "new_talker_rate_sketch",
]


def packet_weights(t: Table) -> jnp.ndarray:
    """Per-row packet multiplicity (1 if the table is one-row-per-packet)."""
    if "n_packets" in t:
        return t["n_packets"]
    return jnp.ones((t.capacity,), jnp.int32)


def traffic_matrix(t: Table) -> GroupResult:
    """A_t(i,j) — ``df.groupby(['src','dst']).value_counts()``.

    Returns group keys (src, dst) and agg ``packets`` = link packet counts.
    """
    return groupby_aggregate(
        [t["src"], t["dst"]],
        {"packets": (packet_weights(t), "sum")},
        n_valid=t.n_valid,
    )


# --- whole-matrix queries ----------------------------------------------------

def valid_packets(t: Table) -> jnp.ndarray:
    """sum_i sum_j A_t(i,j)  ==  1^T A_t 1  ==  df['n_packets'].sum()."""
    w = packet_weights(t)
    return jnp.sum(jnp.where(t.valid_mask(), w, 0))


def unique_links(t: Table) -> jnp.ndarray:
    """|A_t|_0  ==  df[['src','dst']].drop_duplicates().size."""
    return traffic_matrix(t).n_groups


def link_packets(t: Table) -> GroupResult:
    """A_t(i,j) as an explicit (src, dst, packets) edge list."""
    return traffic_matrix(t)


def max_link_packets(t: Table) -> jnp.ndarray:
    """max_ij A_t(i,j)  ==  df.groupby(['src','dst']).value_counts().max()."""
    g = traffic_matrix(t)
    return jnp.max(jnp.where(g.mask(), g.aggs["packets"], 0))


# --- source-side queries ------------------------------------------------------

def unique_sources(t: Table) -> UniqueResult:
    """|1^T A_t|_0 support  ==  df['src'].unique()."""
    return unique(t["src"], n_valid=t.n_valid)


def unique_destinations(t: Table) -> UniqueResult:
    return unique(t["dst"], n_valid=t.n_valid)


def unique_ips(t: Table) -> UniqueResult:
    """Distinct IPs across both endpoints (anonymization domain).

    One packed concat sort (``plan.unique_concat``) — the third and last
    sort of the sort-once query plan.
    """
    g = unique_concat(t["src"], t["dst"], t.n_valid)
    return UniqueResult(
        values=g.keys[0], counts=g.aggs["count"], weight_sums=None,
        n_unique=g.n_groups,
    )


def packets_per_source(t: Table) -> GroupResult:
    """A_t 1  ==  df.groupby('src') packet sums."""
    return groupby_aggregate(
        [t["src"]], {"packets": (packet_weights(t), "sum")}, n_valid=t.n_valid
    )


def max_source_packets(t: Table) -> jnp.ndarray:
    """max(A_t 1)  ==  df.groupby('src').size().max() (weighted)."""
    g = packets_per_source(t)
    return jnp.max(jnp.where(g.mask(), g.aggs["packets"], 0))


def source_fanout(t: Table) -> GroupResult:
    """|A_t|_0 1 — distinct destinations per source.

    Data-science form: ``df[['src','dst']].drop_duplicates()['src'].value_counts()``
    — group the *link* table by src and count.
    """
    links = traffic_matrix(t)
    return groupby_aggregate([links.keys[0]], None, n_valid=links.n_groups)


def max_source_fanout(t: Table) -> jnp.ndarray:
    """max(|A_t|_0 1)  ==  df[['src']].value_counts().max() over links."""
    g = source_fanout(t)
    return jnp.max(jnp.where(g.mask(), g.aggs["count"], 0))


# --- heavy-hitter links (end-to-end pipeline report) --------------------------

@dataclasses.dataclass(frozen=True)
class TopLinks:
    """The k heaviest (src, dst) links; slots past ``n_valid`` are padding."""

    src: jnp.ndarray
    dst: jnp.ndarray
    packets: jnp.ndarray
    n_valid: jnp.ndarray  # scalar int32 == min(k, unique_links)


jax.tree_util.register_dataclass(
    TopLinks, data_fields=["src", "dst", "packets", "n_valid"], meta_fields=[]
)


def top_links(t: Table, k: int) -> TopLinks:
    """``df.groupby(['src','dst']).size().nlargest(k)`` — heaviest links.

    Ties break toward the lexicographically smallest (src, dst) because the
    traffic-matrix group keys are emitted sorted and ``top_k`` prefers the
    lowest index.
    """
    g = traffic_matrix(t)
    k = clamp_k(k, t.capacity)  # top_k clamps identically; keep shapes in step
    pk, idx, n_live = top_k(g.aggs["packets"], k, g.mask())
    keep = jnp.arange(k, dtype=jnp.int32) < n_live
    return TopLinks(
        src=jnp.where(keep, g.keys[0][idx], 0),
        dst=jnp.where(keep, g.keys[1][idx], 0),
        packets=jnp.where(keep, pk, 0),
        n_valid=n_live,
    )


def top_links_from_plan(
    plan: SortedEdges, k: int, links: Optional[GroupResult] = None
) -> TopLinks:
    """:func:`top_links` off a shared plan, sort-free.

    ``lax.top_k`` lowers to a full-length sort; ``argmax_top_k`` selects the
    identical k heaviest links (packet sums are non-negative, so its dtype-
    min caveat never binds) without spending a sort on an already-grouped
    buffer.
    """
    g = link_groups(plan) if links is None else links
    k = clamp_k(k, plan.capacity)
    pk, idx, n_live = argmax_top_k(g.aggs["packets"], k, g.mask())
    keep = jnp.arange(k, dtype=jnp.int32) < n_live
    return TopLinks(
        src=jnp.where(keep, g.keys[0][idx], 0),
        dst=jnp.where(keep, g.keys[1][idx], 0),
        packets=jnp.where(keep, pk, 0),
        n_valid=n_live,
    )


# --- destination-side mirrors -------------------------------------------------

def _swapped(t: Table) -> Table:
    cols = dict(t.columns)
    cols["src"], cols["dst"] = cols["dst"], cols["src"]
    return Table(columns=cols, n_valid=t.n_valid)


def packets_per_destination(t: Table) -> GroupResult:
    return packets_per_source(_swapped(t))


def max_destination_packets(t: Table) -> jnp.ndarray:
    return max_source_packets(_swapped(t))


def destination_fanin(t: Table) -> GroupResult:
    return source_fanout(_swapped(t))


def max_destination_fanin(t: Table) -> jnp.ndarray:
    return max_source_fanout(_swapped(t))


# --- the full challenge query suite -------------------------------------------

@dataclasses.dataclass(frozen=True)
class QueryResults:
    """Scalar results of the challenge suite (vector results exposed as ops)."""

    valid_packets: jnp.ndarray
    unique_links: jnp.ndarray
    max_link_packets: jnp.ndarray
    n_unique_sources: jnp.ndarray
    n_unique_destinations: jnp.ndarray
    n_unique_ips: jnp.ndarray
    max_source_packets: jnp.ndarray
    max_source_fanout: jnp.ndarray
    max_destination_packets: jnp.ndarray
    max_destination_fanin: jnp.ndarray

    def as_dict(self) -> Dict[str, jnp.ndarray]:
        return dataclasses.asdict(self)


jax.tree_util.register_dataclass(
    QueryResults,
    data_fields=[f.name for f in dataclasses.fields(QueryResults)],
    meta_fields=[],
)


def table_plans(t: Table) -> Tuple[SortedEdges, SortedEdges]:
    """The (src-leading, dst-leading) plan pair the whole suite shares."""
    return plan_for_table(t, "src", "dst"), plan_for_table(t, "dst", "src")


def table_csrs(
    t: Table, plans: Optional[Tuple[SortedEdges, SortedEdges]] = None
) -> Tuple[CsrMatrix, CsrMatrix]:
    """(A_t, A_t^T) as CSRs off the shared plan pair — zero extra sorts."""
    plan_src, plan_dst = table_plans(t) if plans is None else plans
    return csr_from_plan(plan_src), csr_from_plan(plan_dst)


def scalar_queries_from_plans(
    t: Table,
    plan_src: SortedEdges,
    plan_dst: SortedEdges,
    ips: Optional[UniqueResult] = None,
    *,
    links: Optional[GroupResult] = None,
    per_src: Optional[GroupResult] = None,
    per_dst: Optional[GroupResult] = None,
    fanout: Optional[GroupResult] = None,
    fanin: Optional[GroupResult] = None,
) -> QueryResults:
    """All ten Table III scalars off the shared plans.

    Sort budget: zero beyond the plans themselves (+ the packed concat sort
    of ``unique_ips`` when ``ips`` is not supplied by the caller).  Callers
    that already derived the group results for their own outputs (the
    challenge ``analyze``) pass them in so eager execution does not repeat
    the segment reductions (under jit XLA CSE dedupes them either way).
    """
    links = link_groups(plan_src) if links is None else links
    per_src = lead_groups(plan_src) if per_src is None else per_src
    per_dst = lead_groups(plan_dst) if per_dst is None else per_dst
    fanout = lead_fanout(plan_src) if fanout is None else fanout
    fanin = lead_fanout(plan_dst) if fanin is None else fanin
    if ips is None:
        ips = unique_ips(t)
    return QueryResults(
        valid_packets=valid_packets(t),
        unique_links=links.n_groups,
        max_link_packets=masked_max(links.aggs["packets"], links.mask()),
        n_unique_sources=per_src.n_groups,
        n_unique_destinations=per_dst.n_groups,
        n_unique_ips=ips.n_unique,
        max_source_packets=masked_max(per_src.aggs["packets"], per_src.mask()),
        max_source_fanout=masked_max(fanout.aggs["count"], fanout.mask()),
        max_destination_packets=masked_max(per_dst.aggs["packets"], per_dst.mask()),
        max_destination_fanin=masked_max(fanin.aggs["count"], fanin.mask()),
    )


def run_all_queries(
    t: Table, plans: Optional[Tuple[SortedEdges, SortedEdges]] = None
) -> QueryResults:
    """Compute every scalar challenge statistic in one jit-able call.

    Sort-once query planning (DESIGN.md §2.3): the whole scalar suite runs
    off one src-leading and one dst-leading packed sort (plus the half-domain
    concat sort of ``unique_ips``) instead of ~7 independent group-by sorts.
    Pass ``plans`` to share the pair with other consumers (the challenge
    ``analyze`` fans them out to the vector, windowed and top-k suites too).
    """
    plan_src, plan_dst = table_plans(t) if plans is None else plans
    return scalar_queries_from_plans(t, plan_src, plan_dst)


# --- detection queries (tier-agnostic) ----------------------------------------
#
# Each detector consumes only *summaries* — key lists and cardinalities —
# so the same function runs on the exact tier (TopLinks / UniqueResult off
# the CSR path) and the sketch tier (space-saving tables / HyperLogLog
# registers, core.sketch).  On the sketch tier the answer inherits that
# tier's error bounds: the space-saving superset guarantee means a truly
# heavy new link cannot be missed by the drift detector, and the HLL
# tolerance bounds the new-talker-rate error (METHODOLOGY.md).


def top_k_drift(
    prev_keys: Sequence[jnp.ndarray],
    prev_n,
    cur_keys: Sequence[jnp.ndarray],
    cur_n,
) -> jnp.ndarray:
    """Fraction of the current top-k keys absent from the previous top-k.

    Stationary traffic keeps the same heavy hitters window over window
    (drift ~ 0); a DDoS burst or scan sweep replaces them wholesale
    (drift → 1).  Keys may be multi-column (links: src + dst).  Returns a
    float32 scalar in [0, 1]; 0 when the current set is empty.
    """
    cur_n = jnp.asarray(cur_n, jnp.int32)
    member = semi_join(cur_keys, prev_keys, cur_n, prev_n)
    cap = cur_keys[0].shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < cur_n
    n_new = jnp.sum((live & ~member).astype(jnp.int32))
    return n_new.astype(jnp.float32) / jnp.maximum(cur_n, 1).astype(jnp.float32)


def top_links_drift(prev: TopLinks, cur: TopLinks) -> jnp.ndarray:
    """:func:`top_k_drift` over two heavy-link reports (either tier: the
    exact :func:`top_links` result or the sketch tier's space-saving table
    repacked as :class:`TopLinks` by ``core.sketch``/``stream.engine``)."""
    return top_k_drift(
        [prev.src, prev.dst], prev.n_valid, [cur.src, cur.dst], cur.n_valid
    )


def new_talker_rate(prev_card, union_card, cur_card) -> jnp.ndarray:
    """Share of this window's distinct sources never seen before.

    Pure cardinality arithmetic — ``(|prev ∪ cur| - |prev|) / |cur|`` — so
    any tier that can report the three cardinalities can answer it.  Botnet
    beaconing keeps the rate near 0 (the same bots recur); spoofed-source
    DDoS pins it near 1.  Clipped to [0, 1] (estimates may jitter).
    """
    prev_card = jnp.asarray(prev_card, jnp.float32)
    union_card = jnp.asarray(union_card, jnp.float32)
    cur_card = jnp.asarray(cur_card, jnp.float32)
    rate = (union_card - prev_card) / jnp.maximum(cur_card, 1.0)
    return jnp.clip(rate, 0.0, 1.0)


def new_talker_rate_exact(
    prev: UniqueResult, cur: UniqueResult
) -> jnp.ndarray:
    """Exact-tier new-talker rate: membership of this window's distinct
    sources against the previous distinct-source set (one binary-search
    probe per key — both lists are already the sorted ``unique`` output)."""
    member = isin(cur.values, prev.values, prev.n_unique, cur.n_unique)
    cap = cur.values.shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < jnp.asarray(cur.n_unique, jnp.int32)
    n_new = jnp.sum((live & ~member).astype(jnp.int32))
    return n_new.astype(jnp.float32) / jnp.maximum(
        jnp.asarray(cur.n_unique, jnp.float32), 1.0
    )


def new_talker_rate_sketch(
    prev_registers: jnp.ndarray, cur_registers: jnp.ndarray
) -> jnp.ndarray:
    """Sketch-tier new-talker rate from two HyperLogLog register banks.

    The union cardinality is free — HLL registers merge by element-wise
    max — so the rate is three :func:`repro.core.sketch.hll_cardinality`
    calls on fixed-size state, never a pass over the raw keys.
    """
    from .sketch import hll_cardinality

    prev_card = hll_cardinality(prev_registers)
    union_card = hll_cardinality(jnp.maximum(prev_registers, cur_registers))
    cur_card = hll_cardinality(cur_registers)
    return new_talker_rate(prev_card, union_card, cur_card)

