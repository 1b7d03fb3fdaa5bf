"""Sort-once query planning — the ``SortedEdges`` plan (DESIGN.md §2.3).

The engine's primitive is "stable sort + segment reduction" (§2), and the
analytics suite used to pay for it per *call site*: ``analyze()`` issued ~10
independent full-buffer sorts whose shared work XLA CSE could not dedupe
(different key orders, different operand sets).  This module restructures the
suite around the observation that **one lexicographic (src, dst) sort exposes
group structure at two granularities simultaneously**:

  * link level — adjacent-inequality on (src, dst) gives the distinct-link
    segmentation (the traffic matrix A_t);
  * leading-endpoint level — src groups are *prefixes* of the same lex
    order, so per-source aggregates, source fan-out and distinct sources
    derive from the identical sorted stream with ZERO additional sorts.

A ``SortedEdges`` value is that sorted stream plus both segmentations; the
derivation helpers below reproduce the exact ``GroupResult``/``UniqueResult``
buffers the naive per-query group-bys emit (bit-identical, including tail
padding), so consumers swap wholesale.  A mirrored dst-leading plan covers
the destination side; distinct IPs take one packed concat sort
(:func:`unique_concat`, or :func:`unique_concat_inverse` when each row's
rank is wanted too).  The sorts themselves are the packed single-operand
uint64 sorts of :mod:`repro.core.ops`.

The plan is a pytree: it crosses ``jit``/``shard_map`` boundaries and can be
built once per table and fanned out to every query.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..compat import enable_x64
from .ops import (
    GroupResult,
    UniqueResult,
    _bias_u32,
    _fuse_u64,
    _scatter_firsts,
    _split_u64,
    _unbias_u32,
    groupby_aggregate,
    multi_key_sort,
    packable_keys,
    segment_ids_from_sorted,
)
from .table import Table

__all__ = [
    "SortedEdges",
    "sorted_edges",
    "plan_for_table",
    "link_groups",
    "lead_groups",
    "lead_fanout",
    "unique_lead",
    "unique_concat",
    "unique_concat_inverse",
    "count_hlo_sorts",
]


@dataclasses.dataclass(frozen=True)
class SortedEdges:
    """One packed lex sort of an edge table, with both segmentations.

    ``key0``/``key1`` are the sorted leading/trailing endpoint columns (live
    prefix of ``n_valid`` rows, tail undefined), ``w`` the per-row weights
    and ``row`` the original row index of each sorted row (the inverse
    permutation — consumers gather auxiliary columns such as window ids
    through it).

    ``seg``/``first``/``n_links`` segment the stream at (key0, key1)
    granularity, ``k0_seg``/``k0_first``/``n_k0`` at key0 granularity; both
    follow the :func:`repro.core.ops.segment_ids_from_sorted` conventions
    (padding rows carry segment id == capacity).
    """

    key0: jnp.ndarray
    key1: jnp.ndarray
    w: jnp.ndarray
    row: jnp.ndarray
    n_valid: jnp.ndarray  # scalar int32
    seg: jnp.ndarray
    first: jnp.ndarray
    n_links: jnp.ndarray  # scalar int32
    k0_seg: jnp.ndarray
    k0_first: jnp.ndarray
    n_k0: jnp.ndarray  # scalar int32

    @property
    def capacity(self) -> int:
        return self.key0.shape[0]

    def valid_rows(self) -> jnp.ndarray:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.n_valid

    def link_mask(self) -> jnp.ndarray:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.n_links

    def k0_mask(self) -> jnp.ndarray:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.n_k0

    def link_to_k0(self) -> jnp.ndarray:
        """(capacity + 1,) map link id -> key0 group id (capacity for pad)."""
        cap = self.capacity
        dst = jnp.where(self.first.astype(bool), self.seg, cap)
        return jnp.full((cap + 1,), cap, jnp.int32).at[dst].set(self.k0_seg)


jax.tree_util.register_dataclass(
    SortedEdges,
    data_fields=[f.name for f in dataclasses.fields(SortedEdges)],
    meta_fields=[],
)


def sorted_edges(
    key0: jnp.ndarray,
    key1: jnp.ndarray,
    weights: Optional[jnp.ndarray] = None,
    n_valid: Optional[jnp.ndarray] = None,
    valid_mask: Optional[jnp.ndarray] = None,
) -> SortedEdges:
    """Build the plan: ONE packed (key0, key1) sort, both segmentations.

    The second (key0-level) segmentation costs only an adjacent-inequality
    pass over the already-sorted stream — key0 groups are prefixes of the
    lex order.
    """
    key0 = jnp.asarray(key0)
    key1 = jnp.asarray(key1)
    cap = key0.shape[0]
    if weights is None:
        weights = jnp.ones((cap,), jnp.int32)
    if valid_mask is not None:
        n_valid = jnp.sum(valid_mask).astype(jnp.int32)
    else:
        n_valid = jnp.asarray(cap if n_valid is None else n_valid, jnp.int32)
    rows = jnp.arange(cap, dtype=jnp.int32)
    (s0, s1), (sw, srow) = multi_key_sort(
        [key0, key1], [weights, rows],
        n_valid=None if valid_mask is not None else n_valid,
        valid_mask=valid_mask,
    )
    seg, first, n_links = segment_ids_from_sorted([s0, s1], n_valid)
    k0_seg, k0_first, n_k0 = segment_ids_from_sorted([s0], n_valid)
    return SortedEdges(
        key0=s0, key1=s1, w=sw, row=srow, n_valid=n_valid,
        seg=seg, first=first, n_links=n_links,
        k0_seg=k0_seg, k0_first=k0_first, n_k0=n_k0,
    )


def plan_for_table(t: Table, lead: str = "src", trail: str = "dst") -> SortedEdges:
    """Plan over a packet table (weights = ``n_packets`` when present)."""
    w = t["n_packets"] if "n_packets" in t else None
    return sorted_edges(t[lead], t[trail], weights=w, n_valid=t.n_valid)


# -----------------------------------------------------------------------------
# derivations — each reproduces a naive group-by's buffers bit-for-bit
# -----------------------------------------------------------------------------

def _segsum(values: jnp.ndarray, seg: jnp.ndarray, cap: int) -> jnp.ndarray:
    return jax.ops.segment_sum(values, seg, num_segments=cap + 1)[:cap]


def link_groups(plan: SortedEdges, packets_name: str = "packets") -> GroupResult:
    """The traffic matrix A_t: ``groupby([key0, key1]).agg(count, sum(w))``."""
    cap = plan.capacity
    valid = plan.valid_rows()
    keys = (
        _scatter_firsts(plan.key0, plan.seg, plan.first, cap),
        _scatter_firsts(plan.key1, plan.seg, plan.first, cap),
    )
    aggs = {
        "count": _segsum(valid.astype(jnp.int32), plan.seg, cap),
        packets_name: _segsum(jnp.where(valid, plan.w, 0), plan.seg, cap),
    }
    return GroupResult(keys=keys, aggs=aggs, n_groups=plan.n_links)


def lead_groups(plan: SortedEdges, packets_name: str = "packets") -> GroupResult:
    """``groupby([key0]).agg(count, sum(w))`` — zero additional sorts."""
    cap = plan.capacity
    valid = plan.valid_rows()
    keys = (_scatter_firsts(plan.key0, plan.k0_seg, plan.k0_first, cap),)
    aggs = {
        "count": _segsum(valid.astype(jnp.int32), plan.k0_seg, cap),
        packets_name: _segsum(jnp.where(valid, plan.w, 0), plan.k0_seg, cap),
    }
    return GroupResult(keys=keys, aggs=aggs, n_groups=plan.n_k0)


def lead_fanout(plan: SortedEdges) -> GroupResult:
    """Distinct key1 per key0 over the link table (fan-out / fan-in).

    Naive form: ``groupby([links.keys[0]], None, n_valid=links.n_groups)``
    — a second full sort of the link buffer.  Here: links are counted into
    their key0 group by summing link-first flags, zero sorts.
    """
    cap = plan.capacity
    keys = (_scatter_firsts(plan.key0, plan.k0_seg, plan.k0_first, cap),)
    counts = _segsum(plan.first, plan.k0_seg, cap)
    return GroupResult(keys=keys, aggs={"count": counts}, n_groups=plan.n_k0)


def unique_lead(plan: SortedEdges) -> UniqueResult:
    """``unique(key0)`` with row multiplicities — zero additional sorts."""
    cap = plan.capacity
    valid = plan.valid_rows()
    return UniqueResult(
        values=_scatter_firsts(plan.key0, plan.k0_seg, plan.k0_first, cap),
        counts=_segsum(valid.astype(jnp.int32), plan.k0_seg, cap),
        weight_sums=None,
        n_unique=plan.n_k0,
    )


def unique_concat(
    a: jnp.ndarray,
    b: jnp.ndarray,
    n_valid: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    count_name: Optional[str] = "count",
) -> GroupResult:
    """Distinct values of ``concat(a, b)`` — ONE packed half-domain sort.

    ``a`` and ``b`` share a live prefix of ``n_valid`` rows; the two live
    blocks are compacted against each other with a gather so the (2*cap,)
    concat sorts with a plain prefix-validity packed key.  ``positions``
    (laid out like the concat: a-rows then b-rows) adds a ``first_pos`` min
    aggregate — the streaming dictionary's first-appearance rule.  This is
    both ``unique_ips`` (the anonymization domain) and the stream engine's
    batch-candidate extraction.
    """
    cap = a.shape[0]
    n_valid = jnp.asarray(n_valid, jnp.int32)
    both = jnp.concatenate([jnp.asarray(a), jnp.asarray(b)])
    idx = jnp.arange(2 * cap, dtype=jnp.int32)
    shifted = jnp.where(idx < n_valid, idx, idx - n_valid + cap)
    sel = jnp.where(idx < 2 * n_valid, shifted, 0)
    compact = both[sel]
    values = None
    if positions is not None:
        values = {"first_pos": (jnp.asarray(positions)[sel], "min")}
    return groupby_aggregate(
        [compact], values, n_valid=2 * n_valid, count_name=count_name
    )


def unique_concat_inverse(
    a: jnp.ndarray, b: jnp.ndarray, n_valid: jnp.ndarray
) -> Tuple[GroupResult, jnp.ndarray, jnp.ndarray]:
    """:func:`unique_concat` plus each live row's rank among the distinct
    values — ``np.unique(return_inverse=True)`` off the same ONE sort.

    The packed uint64 key carries, below the validity flag and the biased
    32-bit value, each compact slot's position in ⌈log2(2·cap)⌉ low bits
    (56 bits at 2^22 rows, 64 at 2^30).  Positions are distinct and
    ascending, so the sort orders the values as the stable sort of
    :func:`unique_concat` does, and the ``GroupResult`` equals its
    ``count_name=None`` result bit for bit.  The segment id of each sorted
    slot, scattered back to its position, is its rank: no binary search
    over the distinct values.  Returns ``(group, rank_a, rank_b)``; ranks
    of padding rows (>= ``n_valid``) are 0.  The sort runs in scope
    ``unique``, the scatter in ``factorize``.
    """
    a, b = jnp.asarray(a), jnp.asarray(b)
    if not packable_keys([a, b]):
        raise ValueError("unique_concat_inverse requires int32/uint32 columns")
    cap = a.shape[0]
    m = 2 * cap
    pos_bits = max(1, (m - 1).bit_length())
    if pos_bits > 31:
        raise ValueError(f"2 * capacity {m} leaves no room for positions")
    n_valid = jnp.asarray(n_valid, jnp.int32)
    rows = jnp.arange(cap, dtype=jnp.int32)
    with jax.named_scope("unique"):
        both = jnp.concatenate([a, b])
        idx = jnp.arange(m, dtype=jnp.int32)
        shifted = jnp.where(idx < n_valid, idx, idx - n_valid + cap)
        compact = both[jnp.where(idx < 2 * n_valid, shifted, 0)]
        # the live slots are the prefix [0, 2·n_valid) before the sort and
        # after it (the flag sends padding last), so one mask serves both
        live = idx < 2 * n_valid
        u = _bias_u32(compact)
        lo = (u << pos_bits) | idx.astype(jnp.uint32)
        hi = (u >> (32 - pos_bits)) | ((~live).astype(jnp.uint32) << pos_bits)
        with enable_x64():
            packed = lax.sort(_fuse_u64(hi, lo), is_stable=True)
        shi, slo = _split_u64(packed)
        pos = (slo & jnp.uint32((1 << pos_bits) - 1)).astype(jnp.int32)
        svals = _unbias_u32((slo >> pos_bits) | (shi << (32 - pos_bits)),
                            compact.dtype)
        seg, first, n_groups = segment_ids_from_sorted([svals], 2 * n_valid)
        group = GroupResult(keys=(_scatter_firsts(svals, seg, first, m),),
                            aggs={}, n_groups=n_groups)
    with jax.named_scope("factorize"):
        # pos is a permutation of [0, m): one scatter, every slot written
        inv = jnp.zeros((m,), jnp.int32).at[pos].set(
            jnp.where(live, seg, 0), unique_indices=True)
        rank_a = jnp.where(rows < n_valid, inv[:cap], 0)
        rank_b = jnp.where(rows < n_valid,
                           lax.dynamic_slice(inv, (n_valid,), (cap,)), 0)
    return group, rank_a, rank_b


# -----------------------------------------------------------------------------
# HLO sort accounting (the plan's budget, asserted in tests / benchmarks)
# -----------------------------------------------------------------------------

_SORT_DEF = re.compile(r"=\s[^=]*\bsort\(")
_DIM = re.compile(r"\[(\d+)")


def count_hlo_sorts(hlo_text: str, min_rows: int = 0) -> int:
    """Count sort ops in (compiled) HLO text with leading dim >= min_rows.

    Feed it ``jax.jit(fn).lower(*args).compile().as_text()`` — the
    post-optimization module, after CSE — so the count is what actually
    executes.  ``lax.top_k`` lowerings that expand to sorts are counted
    too: a sort is a sort.
    """
    n = 0
    for line in hlo_text.splitlines():
        if _SORT_DEF.search(line):
            dims = [int(d) for d in _DIM.findall(line)]
            if dims and max(dims) >= min_rows:
                n += 1
    return n
