"""Distributed IP anonymization — one global bijection over sharded rows.

Extends paper §IV to a row-sharded table: the anonymized id assignment must
be a single consistent bijection onto ``[0, n_ips)`` across every shard.

  1. each shard extracts its local distinct IPs;
  2. IPs route to owner shards by hash — an IP appearing on many shards
     lands on exactly one owner, which deduplicates it;
  3. owners carve disjoint id ranges out of ``[0, n_ips)`` (all_gather of
     the owned counts + prefix sum) and shuffle within their range
     (``random_permutation`` keyed per owner);
  4. the assigned ids ride the inverse ``all_to_all`` route back to every
     asking shard (``return_to_sender``), which gathers them onto its rows.

Randomness note: the composition (hash route × per-owner shuffle) is a
bijection but not a uniform permutation over [0, n_ips); the challenge's
anonymization contract (graph isomorphism, ``ref_anonymize_check``) does
not require uniformity.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..core.ops import factorize, mix32, random_permutation, unique
from ..core.queries import unique_ips
from ..core.table import Table
from .exchange import exchange_by_owner, return_to_sender

__all__ = ["distributed_anonymize"]


def distributed_anonymize(
    t: Table, key: jax.Array, axis_name, overflow_factor: float = 2.0
) -> Dict[str, jnp.ndarray]:
    """Anonymize ``src``/``dst`` of a row-sharded packet table.

    Call inside ``shard_map``; ``key`` must be replicated.  Returns
    ``{"src", "dst"}`` (this shard's anonymized columns), ``"n_ips"`` and
    ``"overflow"`` (replicated scalars).  If ``overflow > 0`` the mapping is
    incomplete — callers must treat the batch as failed and retry with a
    larger ``overflow_factor``.
    """
    n_shards = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)

    ips = unique_ips(t)  # local distinct, tail-padded
    (r_ip,), r_valid, slot, ov = exchange_by_owner(
        (mix32(ips.values) % jnp.uint32(n_shards)).astype(jnp.int32),
        [ips.values],
        ips.mask(),
        axis_name,
        overflow_factor=overflow_factor,
    )

    # owner side: dedupe, carve this owner's id range, shuffle within it
    owned = unique(r_ip, valid_mask=r_valid)
    counts = lax.all_gather(owned.n_unique, axis_name)  # (n_shards,)
    base = jnp.cumsum(counts)[me] - counts[me]
    recv_cap = r_ip.shape[0]
    perm = random_permutation(
        jax.random.fold_in(key, me), recv_cap, owned.n_unique
    )
    rank = factorize(r_ip, owned.values)  # per received slot
    reply = jnp.where(r_valid, base + perm[rank], 0).astype(jnp.int32)

    # inverse route: each local distinct IP learns its global id
    new_ids = return_to_sender(reply, slot, axis_name)
    new_ids = jnp.where(slot >= 0, new_ids, 0)

    # gather onto rows (rows whose IP overflowed get id 0 — see overflow)
    src_rank = factorize(t["src"], ips.values)
    dst_rank = factorize(t["dst"], ips.values)
    return {
        "src": new_ids[src_rank],
        "dst": new_ids[dst_rank],
        "n_ips": lax.psum(owned.n_unique, axis_name),
        "overflow": lax.psum(ov, axis_name),
    }
