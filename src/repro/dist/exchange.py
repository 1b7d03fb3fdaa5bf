"""Hash-partition ``all_to_all`` exchange — the static-shape shuffle.

cuDF's multi-GPU relational ops repartition rows with a dynamic hash shuffle;
under shard_map every buffer is static, so the exchange here routes rows to
their owner shard through fixed-size per-peer buckets (DESIGN.md §5):

  * every valid row has an ``owner`` shard id (callers hash keys with
    :func:`repro.core.ops.mix32`);
  * rows are sorted by owner and scattered into a ``(n_shards, bucket)`` send
    buffer, one bucket per peer — the owner sort is a packed single-operand
    uint64 sort (validity flag in the high word, owner id in the low word;
    DESIGN.md §2.3), so the per-shard routing cost is one integer-key sort
    rather than a (validity, owner) comparator sort;
  * ``lax.all_to_all`` swaps buckets; received rows carry an arbitrary
    validity *mask* (not a prefix) — exactly the layout
    :func:`repro.core.ops.groupby_aggregate` accepts via ``valid_mask``;
  * rows beyond a bucket's capacity are **counted, never silently dropped**:
    the overflow count is returned so callers can psum and report it.

The exchange also returns each row's send-buffer slot, which makes the
route *invertible*: an owner can compute per-received-slot answers and
``all_to_all`` them straight back (dist/anonymize.py uses this to return
anonymized ids to the shards that asked).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
from jax import lax

from ..core.ops import mix32, multi_key_sort, segment_ids_from_sorted
from ..core.sparse import CsrMatrix, from_coo

__all__ = [
    "bucket_size",
    "exchange_by_owner",
    "exchange_csr",
    "return_to_sender",
]


def bucket_size(capacity: int, n_shards: int, overflow_factor: float) -> int:
    """Per-peer bucket rows so the receive buffer is capacity*overflow_factor."""
    return max(1, int(capacity * overflow_factor) // n_shards)


def exchange_by_owner(
    owner: jnp.ndarray,
    cols: Sequence[jnp.ndarray],
    valid: jnp.ndarray,
    axis_name,
    *,
    overflow_factor: float = 2.0,
) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Route each valid row to shard ``owner[i]``.

    Args:
      owner: (capacity,) int32 target shard per row (ignored where invalid).
      cols: payload columns, each (capacity,).
      valid: (capacity,) bool live-row mask.
      axis_name: shard_map axis name (or tuple of names).
      overflow_factor: receive/send buffer headroom over ``capacity``.

    Returns ``(recv_cols, recv_valid, slot, overflow)``:
      recv_cols: each (n_shards * bucket,) — rows this shard now owns;
        ``recv[s*bucket + p]`` came from shard ``s``.
      recv_valid: (n_shards * bucket,) bool mask of live received rows.
      slot: (capacity,) int32 — flat send-buffer slot each local row went to
        (-1 for invalid or overflowed rows); feed to :func:`return_to_sender`.
      overflow: scalar int32 — local valid rows that did not fit their bucket.
    """
    cols = [jnp.asarray(c) for c in cols]
    cap = owner.shape[0]
    n_shards = lax.axis_size(axis_name)
    bucket = bucket_size(cap, n_shards, overflow_factor)
    n_send = n_shards * bucket

    n_valid = jnp.sum(valid).astype(jnp.int32)
    row_idx = jnp.arange(cap, dtype=jnp.int32)
    # sort rows by owner (valid prefix first) so each owner's rows are a run;
    # single-key int32 + mask routes through the packed uint64 sort exactly
    # (the 1-key layout spends a spare word bit on validity — no collisions)
    (s_owner,), (s_row,) = multi_key_sort(
        [owner.astype(jnp.int32)], [row_idx], valid_mask=valid
    )
    seg, first, _ = segment_ids_from_sorted([s_owner], n_valid)
    # rank of each row within its owner run
    run_start = (
        jnp.zeros((cap + 1,), jnp.int32)
        .at[jnp.where(first.astype(bool), seg, cap)]
        .set(row_idx)
    )
    pos = row_idx - run_start[seg]
    in_prefix = row_idx < n_valid
    fits = in_prefix & (pos < bucket)
    s_slot = jnp.where(fits, s_owner * bucket + pos, n_send)  # n_send = dump
    overflow = jnp.sum(in_prefix & ~fits).astype(jnp.int32)

    send_valid = jnp.zeros((n_send + 1,), jnp.bool_).at[s_slot].set(fits)[:n_send]
    recv_valid = _swap(send_valid, axis_name, n_shards, bucket)
    recv_cols = []
    for c in cols:
        buf = jnp.zeros((n_send + 1,), c.dtype).at[s_slot].set(c[s_row])[:n_send]
        recv_cols.append(_swap(buf, axis_name, n_shards, bucket))

    # map slots back to original row order
    slot = (
        jnp.full((cap,), -1, jnp.int32)
        .at[s_row]
        .set(jnp.where(fits, s_slot, -1).astype(jnp.int32))
    )
    return tuple(recv_cols), recv_valid, slot, overflow


def exchange_csr(
    csr: CsrMatrix,
    axis_name,
    *,
    overflow_factor: float = 2.0,
) -> Tuple[CsrMatrix, jnp.ndarray]:
    """Row-partition a local CSR across shards: every shard ends up owning
    complete rows (DESIGN.md §2.4 / §5).

    Each stored entry is routed to the owner shard of its *leading row key*
    (``mix32`` hash), so all fragments of a row — one per contributing
    shard — land on the same owner; the owner rebuilds its shard of the
    global matrix with one duplicate-collapsing :func:`from_coo` (plus
    monoid: coincident coordinates from different shards add).  Row counts,
    nnz and row reductions of the owned CSRs are then globally exact under
    ``psum``/``pmax`` — the key spaces are disjoint by construction.

    Returns ``(owned_csr, overflow)``; ``overflow`` counts entries that
    missed their per-peer bucket (skewed keys) plus owner-side drops —
    reported, never silent, per the exchange contract.
    """
    n_shards = lax.axis_size(axis_name)
    rows = csr.entry_rows()
    row_cols = [csr.entry_row_key(i, rows) for i in range(len(csr.row_keys))]
    owner = (mix32(row_cols[0]) % jnp.uint32(n_shards)).astype(jnp.int32)
    recv, recv_valid, _, ov = exchange_by_owner(
        owner,
        [*row_cols, csr.col_keys, csr.vals],
        csr.entry_mask(),
        axis_name,
        overflow_factor=overflow_factor,
    )
    *r_rows, r_cols, r_vals = recv
    owned, dropped = from_coo(
        r_rows, r_cols, r_vals, valid_mask=recv_valid, op="plus"
    )
    return owned, ov + dropped


def return_to_sender(
    reply: jnp.ndarray, slot: jnp.ndarray, axis_name
) -> jnp.ndarray:
    """Send per-received-slot answers back along the inverse route.

    ``reply`` is laid out like the receive buffer of :func:`exchange_by_owner`
    on the *owner* side; the result, gathered at ``slot`` (where >= 0), is
    each original row's answer on the *sender* side.
    """
    n_shards = lax.axis_size(axis_name)
    bucket = reply.shape[0] // n_shards
    back = _swap(reply, axis_name, n_shards, bucket)
    safe = jnp.where(slot >= 0, slot, 0)
    return back[safe]


def _swap(flat: jnp.ndarray, axis_name, n_shards: int, bucket: int) -> jnp.ndarray:
    """all_to_all a flat (n_shards * bucket,) buffer, bucket i -> peer i."""
    out = lax.all_to_all(
        flat.reshape(n_shards, bucket), axis_name, split_axis=0, concat_axis=0,
        tiled=True,
    )
    return out.reshape(n_shards * bucket)
