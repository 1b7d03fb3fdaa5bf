"""Distributed challenge queries: row-partitioned CSR shards + merge.

The paper runs the 14 Table III queries on one GPU; at 2^30+ packets the
edge table outgrows a single chip, so this module re-derives every scalar
statistic exactly under row sharding (DESIGN.md §5):

  1. each shard reduces its rows to a local CSR traffic matrix
     (``core.sparse.csr_from_plan`` over the local sort-once plan) — the
     hypersparse regime makes this the big data reduction;
  2. CSR shards are row-partitioned by key hash (``mix32`` via
     ``exchange_csr``): a src-rowed matrix for source-side statistics, a
     dst-rowed mirror for destination-side, so every row — and therefore
     every link and every per-endpoint group — is wholly owned by exactly
     one shard;
  3. owners rebuild their shard of the global matrix with one
     duplicate-collapsing ``from_coo`` and answer in matrix language —
     ``n_rows``/``nnz`` counts, ``reduce_rows`` (A·1), ``degrees``
     (|A|_0·1) — and scalars merge with ``psum``/``pmax``.

Ownership makes the counts exact — distinct counts add across shards because
key spaces are disjoint.  Bucket overflow (skewed keys) is reported in the
``overflow`` field, never silent: count-statistics may undercount iff
``overflow > 0``.  The pre-CSR formulation (flat link-table exchange + two
owner-side group-bys per side) is kept as
:func:`distributed_queries_naive` — the A/B baseline, identical outputs.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
from jax import lax

from ..core.ops import groupby_aggregate, masked_max, mix32, unique
from ..core.queries import packet_weights, table_csrs, unique_ips
from ..core.sparse import degrees, reduce_rows
from ..core.table import Table
from .exchange import exchange_by_owner, exchange_csr

__all__ = [
    "distributed_queries",
    "distributed_queries_naive",
    "distributed_unique_count",
]


def _owner_of(keys: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    return (mix32(keys) % jnp.uint32(n_shards)).astype(jnp.int32)


def distributed_queries(
    t: Table, axis_name, overflow_factor: float = 2.0
) -> Dict[str, jnp.ndarray]:
    """All scalar Table III statistics over a row-sharded packet table.

    Call inside ``shard_map`` with ``t``'s columns holding this shard's rows.
    Returns a dict of replicated scalars: the ten ``ref_run_all_queries``
    keys plus ``overflow`` (see module docstring).
    """
    w = packet_weights(t)
    valid = t.valid_mask()

    out: Dict[str, jnp.ndarray] = {
        "valid_packets": lax.psum(jnp.sum(jnp.where(valid, w, 0)), axis_name)
    }
    overflow = jnp.zeros((), jnp.int32)

    # local CSR pair off the local sort-once plans (A_t and A_t^T)
    csr_src, csr_dst = table_csrs(t)
    for side, csr in (("source", csr_src), ("destination", csr_dst)):
        owned, ov = exchange_csr(
            csr, axis_name, overflow_factor=overflow_factor
        )
        overflow = overflow + ov
        if side == "source":
            out["unique_links"] = lax.psum(owned.nnz, axis_name)  # |A|_0
            out["max_link_packets"] = lax.pmax(                   # max(A)
                masked_max(owned.vals, owned.entry_mask()), axis_name
            )
        ep_pk = reduce_rows(owned, "plus")                        # A·1
        fan = degrees(owned)                                      # |A|_0·1
        m = owned.row_mask()
        out[f"n_unique_{side}s"] = lax.psum(owned.n_rows, axis_name)
        out[f"max_{side}_packets"] = lax.pmax(masked_max(ep_pk, m), axis_name)
        fname = "max_source_fanout" if side == "source" else "max_destination_fanin"
        out[fname] = lax.pmax(masked_max(fan, m), axis_name)

    # distinct IPs across both endpoints
    ips = unique_ips(t)
    n_ips, ov = distributed_unique_count(
        ips.values, axis_name,
        valid_mask=ips.mask(), overflow_factor=overflow_factor,
    )
    out["n_unique_ips"] = n_ips
    out["overflow"] = lax.psum(overflow, axis_name) + ov
    return out


def distributed_queries_naive(
    t: Table, axis_name, overflow_factor: float = 2.0
) -> Dict[str, jnp.ndarray]:
    """Pre-CSR formulation: flat link-table exchange + owner group-bys.

    One local (src, dst) group-by, then per side a flat 3-column exchange
    and TWO owner-side group-bys (global links, then per-endpoint).  Kept
    as the A/B baseline for :func:`distributed_queries` — identical
    outputs, exercised by tests/_distributed_worker.py.
    """
    n_shards = lax.axis_size(axis_name)
    w = packet_weights(t)
    valid = t.valid_mask()

    out: Dict[str, jnp.ndarray] = {
        "valid_packets": lax.psum(jnp.sum(jnp.where(valid, w, 0)), axis_name)
    }

    # local distinct links with local packet sums
    links = groupby_aggregate(
        [t["src"], t["dst"]], {"packets": (w, "sum")}, n_valid=t.n_valid
    )
    overflow = jnp.zeros((), jnp.int32)

    for side, key_idx in (("source", 0), ("destination", 1)):
        (r_src, r_dst, r_pk), r_valid, _, ov = exchange_by_owner(
            _owner_of(links.keys[key_idx], n_shards),
            [links.keys[0], links.keys[1], links.aggs["packets"]],
            links.mask(),
            axis_name,
            overflow_factor=overflow_factor,
        )
        overflow = overflow + ov
        # owner-side global links (same link may arrive from several shards)
        glinks = groupby_aggregate(
            [r_src, r_dst], {"packets": (r_pk, "sum")}, valid_mask=r_valid
        )
        if side == "source":
            out["unique_links"] = lax.psum(glinks.n_groups, axis_name)
            out["max_link_packets"] = lax.pmax(
                masked_max(glinks.aggs["packets"], glinks.mask()), axis_name
            )
        # per-endpoint over owned links: count == fan-out/in, sum == packets
        ep = groupby_aggregate(
            [glinks.keys[key_idx]],
            {"packets": (glinks.aggs["packets"], "sum")},
            n_valid=glinks.n_groups,
        )
        m = ep.mask()
        out[f"n_unique_{side}s"] = lax.psum(ep.n_groups, axis_name)
        out[f"max_{side}_packets"] = lax.pmax(
            masked_max(ep.aggs["packets"], m), axis_name
        )
        fan = "max_source_fanout" if side == "source" else "max_destination_fanin"
        out[fan] = lax.pmax(masked_max(ep.aggs["count"], m), axis_name)

    # distinct IPs across both endpoints
    ips = unique_ips(t)
    n_ips, ov = distributed_unique_count(
        ips.values, axis_name,
        valid_mask=ips.mask(), overflow_factor=overflow_factor,
    )
    out["n_unique_ips"] = n_ips
    out["overflow"] = lax.psum(overflow, axis_name) + ov
    return out


def distributed_unique_count(
    x: jnp.ndarray,
    axis_name,
    valid_mask: jnp.ndarray | None = None,
    overflow_factor: float = 2.0,
):
    """Exact global distinct count of a sharded column.

    Returns ``(count, overflow)`` replicated scalars.  Works over a tuple of
    axes (e.g. ``("pod", "rows")``) — the hash route then crosses pods.
    """
    n_shards = lax.axis_size(axis_name)
    if valid_mask is None:
        valid_mask = jnp.ones(x.shape, jnp.bool_)
    # local distinct first: bounds the exchange volume by the local key space
    u = unique(x, valid_mask=valid_mask)
    (r_vals,), r_valid, _, ov = exchange_by_owner(
        _owner_of(u.values, n_shards),
        [u.values],
        u.mask(),
        axis_name,
        overflow_factor=overflow_factor,
    )
    owned = unique(r_vals, valid_mask=r_valid)
    return lax.psum(owned.n_unique, axis_name), lax.psum(ov, axis_name)
