"""Relational primitives ("jaxdf" ops) — the paper's ETL vocabulary in JAX.

The paper expresses every Graph Challenge query with four dataframe ops:
``unique``, ``value_counts``, ``groupby(...).agg``, ``drop_duplicates``.
cuDF implements these with dynamic hash tables; XLA requires static shapes,
so the TPU-idiomatic equivalent is **multi-key stable sort + segment
reduction** (see DESIGN.md §2).  Every op here:

  * takes arrays of static ``capacity`` with the first ``n_valid`` rows live,
  * returns arrays of static capacity with an ``n_groups``/``n_unique`` scalar
    and padding at the tail,
  * is pure jnp/lax, so it jits, vmaps, and shard_maps unchanged.

The invalid tail is handled with a *leading validity sort key*: rows are
sorted by ``(is_invalid, key0, key1, ...)``, which guarantees the first
``n_valid`` sorted rows are exactly the live rows regardless of key values
(including values equal to the dtype max).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..compat import enable_x64

__all__ = [
    "packable_keys",
    "packed_key_words",
    "multi_key_sort",
    "masked_max",
    "clamp_k",
    "argmax_top_k",
    "segment_ids_from_sorted",
    "GroupResult",
    "groupby_aggregate",
    "UniqueResult",
    "unique",
    "value_counts",
    "drop_duplicates",
    "factorize",
    "isin",
    "semi_join",
    "top_k",
    "mix32",
    "random_permutation",
    "hash_permutation",
]

_OVERFLOW = "overflow segment index == capacity; buffers are capacity+1 long"


def _validity_key(capacity: int, n_valid: jnp.ndarray) -> jnp.ndarray:
    """0 for live rows, 1 for padding — used as the leading sort key."""
    return (jnp.arange(capacity, dtype=jnp.int32) >= n_valid).astype(jnp.int32)


# -----------------------------------------------------------------------------
# Packed-key sorting (DESIGN.md §2.3)
#
# A multi-operand ``lax.sort`` evaluates its lexicographic comparator once per
# element pair, touching every key column.  When the keys are one or two
# 32-bit integer columns they fit a single ``uint64`` word — int32 is biased
# to unsigned (sign-bit flip, order-preserving), the leading key takes the
# high word — and the whole sort becomes a SINGLE-operand ``lax.sort`` whose
# comparator is one integer compare.  The validity discipline is preserved
# without spending key bits on it:
#
#   * 1 key: the high word is free, so it carries the validity flag directly
#     (exact for any validity mask — no collisions possible);
#   * 2 keys: invalid rows are sent to ``UINT64_MAX``.  A *valid* row may
#     also legitimately pack to ``UINT64_MAX`` (both keys at the dtype max).
#     With prefix validity (``n_valid``) stability resolves the tie: valid
#     rows precede the padding tail in the input, so the stable sort keeps
#     them ahead of it.  With an arbitrary ``valid_mask`` the tie is instead
#     repaired after the sort by a stable partition on the carried validity
#     payload (one cumsum + scatter — O(n), not a second sort).
#
# 64-bit wrinkle: with x64 off (the default) JAX canonicalizes 64-bit types
# to 32 bits, so the pack/unpack never performs uint64 arithmetic — words
# are assembled in uint32, and only the ``bitcast_convert_type`` that fuses
# (n, 2) uint32 -> (n,) uint64 (XLA defines element 0 of the trailing dim as
# the least-significant word), the sort, and the split back run inside
# ``compat.enable_x64()``.  Wider or non-32-bit key sets fall back to the
# multi-operand comparator sort unchanged.
# -----------------------------------------------------------------------------

_PACKABLE_DTYPES = (jnp.dtype(jnp.int32), jnp.dtype(jnp.uint32))
_U32_SIGN = jnp.uint32(0x80000000)
_U32_MAX = jnp.uint32(0xFFFFFFFF)


def packable_keys(keys: Sequence[jnp.ndarray]) -> bool:
    """True iff ``keys`` fuse into a single uint64 sort key (<= 2 x 32-bit)."""
    return 1 <= len(keys) <= 2 and all(
        k.ndim == 1 and k.dtype in _PACKABLE_DTYPES for k in keys
    )


def _bias_u32(k: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving int32 -> uint32 bias (uint32 passes through)."""
    if k.dtype == jnp.dtype(jnp.uint32):
        return k
    return lax.bitcast_convert_type(k, jnp.uint32) ^ _U32_SIGN


def _unbias_u32(u: jnp.ndarray, dtype) -> jnp.ndarray:
    if jnp.dtype(dtype) == jnp.dtype(jnp.uint32):
        return u
    return lax.bitcast_convert_type(u ^ _U32_SIGN, jnp.int32)


def _fuse_u64(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    pair = jnp.stack([lo, hi], axis=-1)  # element 0 = least-significant word
    with enable_x64():
        return lax.bitcast_convert_type(pair, jnp.uint64)


def _split_u64(packed: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    with enable_x64():
        pair = lax.bitcast_convert_type(packed, jnp.uint32)
    return pair[..., 1], pair[..., 0]


def packed_key_words(
    keys: Sequence[jnp.ndarray],
    invalid: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(hi, lo) uint32 words of the fused key; ``invalid`` rows sort last.

    The packing layout of DESIGN.md §2.3, exposed so future consumers can
    binary-search or compare packed keys without sorting (the sort path
    itself goes through :func:`multi_key_sort`).  See the 2-key caveat in
    the section comment: with ``invalid`` set, a valid all-dtype-max 2-key
    row collides with the invalid sentinel and needs the caller to resolve
    the tie.
    """
    if not packable_keys(keys):
        raise ValueError("packed_key_words requires 1-2 int32/uint32 keys")
    if len(keys) == 1:
        hi = (
            jnp.zeros(keys[0].shape, jnp.uint32)
            if invalid is None
            else invalid.astype(jnp.uint32)
        )
        lo = _bias_u32(keys[0])
    else:
        hi = _bias_u32(keys[0])
        lo = _bias_u32(keys[1])
        if invalid is not None:
            hi = jnp.where(invalid, _U32_MAX, hi)
            lo = jnp.where(invalid, _U32_MAX, lo)
    return hi, lo


def _stable_partition_perm(valid: jnp.ndarray) -> jnp.ndarray:
    """Gather permutation moving live rows to the prefix, order-preserving."""
    cap = valid.shape[0]
    n_valid = jnp.sum(valid).astype(jnp.int32)
    live_pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    dead_pos = n_valid + jnp.cumsum((~valid).astype(jnp.int32)) - 1
    dest = jnp.where(valid, live_pos, dead_pos)
    return jnp.zeros((cap,), jnp.int32).at[dest].set(
        jnp.arange(cap, dtype=jnp.int32)
    )


def _packed_sort(
    keys: Sequence[jnp.ndarray],
    payloads: Sequence[jnp.ndarray],
    n_valid: Optional[jnp.ndarray],
    valid_mask: Optional[jnp.ndarray],
) -> Tuple[Tuple[jnp.ndarray, ...], Tuple[jnp.ndarray, ...]]:
    """Single-operand uint64 sort implementing the multi_key_sort contract."""
    cap = keys[0].shape[0]
    if valid_mask is not None:
        invalid = ~valid_mask
    elif n_valid is not None:
        invalid = jnp.arange(cap, dtype=jnp.int32) >= n_valid
    else:
        invalid = None
    hi, lo = packed_key_words(keys, invalid)
    packed = _fuse_u64(hi, lo)
    # 2-key + arbitrary mask is the one layout where a valid row can collide
    # with the invalid sentinel — carry validity and repair post-sort.
    repair = len(keys) == 2 and valid_mask is not None
    operands = (packed, *payloads) + ((valid_mask,) if repair else ())
    with enable_x64():
        out = lax.sort(operands, num_keys=1, is_stable=True)
    packed, spayloads = out[0], out[1:]
    shi, slo = _split_u64(packed)  # back to uint32 words before any gather —
    # indexing a uint64 array outside enable_x64 would silently downcast
    if repair:
        *spayloads, svalid = spayloads
        perm = _stable_partition_perm(svalid)
        shi, slo = shi[perm], slo[perm]
        spayloads = [p[perm] for p in spayloads]
    if len(keys) == 1:
        skeys = (_unbias_u32(slo, keys[0].dtype),)
    else:
        skeys = (_unbias_u32(shi, keys[0].dtype), _unbias_u32(slo, keys[1].dtype))
    return skeys, tuple(spayloads)


def multi_key_sort(
    keys: Sequence[jnp.ndarray],
    payloads: Sequence[jnp.ndarray] = (),
    n_valid: Optional[jnp.ndarray] = None,
    valid_mask: Optional[jnp.ndarray] = None,
) -> Tuple[Tuple[jnp.ndarray, ...], Tuple[jnp.ndarray, ...]]:
    """Stable lexicographic sort by ``keys`` carrying ``payloads`` along.

    Live rows come first (see module docstring).  Validity is either a prefix
    (``n_valid``) or an arbitrary boolean ``valid_mask`` (e.g. the segmented
    buffers an ``all_to_all`` exchange produces — dist/relational.py); after
    sorting, live rows always form the prefix.  Returns (sorted_keys,
    sorted_payloads); the validity key is stripped from the output.

    When the keys are one or two 32-bit integer columns the sort routes
    through the packed single-operand uint64 path (DESIGN.md §2.3); the
    result is identical on the live prefix (including payload stability).
    The two paths may order the *garbage tail* differently — rows at
    index >= n_valid are undefined either way, and in the packed 2-key path
    the tail key slots unpack to the dtype max rather than sorted garbage.
    """
    keys = [jnp.asarray(k) for k in keys]
    payloads = [jnp.asarray(p) for p in payloads]
    cap = keys[0].shape[0]
    if packable_keys(keys):
        return _packed_sort(keys, payloads, n_valid, valid_mask)
    if n_valid is None and valid_mask is None:
        operands = (*keys, *payloads)
        out = lax.sort(operands, num_keys=len(keys), is_stable=True)
    else:
        if valid_mask is not None:
            vk = (~valid_mask).astype(jnp.int32)
        else:
            vk = _validity_key(cap, n_valid)
        operands = (vk, *keys, *payloads)
        out = lax.sort(operands, num_keys=1 + len(keys), is_stable=True)[1:]
    return tuple(out[: len(keys)]), tuple(out[len(keys):])


def segment_ids_from_sorted(
    sorted_keys: Sequence[jnp.ndarray], n_valid: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Group structure of pre-sorted keys.

    Returns ``(seg_ids, first_flags, n_groups)`` where ``seg_ids[i]`` is the
    group index of row i (== capacity for padding rows — callers must use
    ``num_segments = capacity + 1`` buffers, see ``_OVERFLOW``), and
    ``first_flags[i]`` is 1 iff row i is the first row of its group.
    """
    cap = sorted_keys[0].shape[0]
    valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
    neq = jnp.zeros(cap, dtype=bool)
    for k in sorted_keys:
        neq = neq | jnp.concatenate([jnp.ones((1,), bool), k[1:] != k[:-1]])
    neq = neq.at[0].set(True)
    first = (neq & valid).astype(jnp.int32)
    seg = jnp.cumsum(first) - 1
    seg = jnp.where(valid, seg, cap).astype(jnp.int32)
    n_groups = jnp.sum(first).astype(jnp.int32)
    return seg, first, n_groups


def _scatter_firsts(
    col: jnp.ndarray, seg: jnp.ndarray, first: jnp.ndarray, cap: int
) -> jnp.ndarray:
    """Scatter first-occurrence values of ``col`` to their group slot.

    Padding slots are filled with the dtype max so that key outputs stay
    globally sorted ascending (live prefix < padding) — ``factorize`` relies
    on this for its binary search.
    """
    dst = jnp.where(first.astype(bool), seg, cap)
    buf = jnp.full((cap + 1,), _max_ident(col.dtype), dtype=col.dtype).at[dst].set(col)
    return buf[:cap]


_AGGS = ("sum", "count", "max", "min", "mean")


@dataclasses.dataclass(frozen=True)
class GroupResult:
    """Result of a group-by: group keys + aggregates, tail-padded."""

    keys: Tuple[jnp.ndarray, ...]
    aggs: Dict[str, jnp.ndarray]
    n_groups: jnp.ndarray  # scalar int32

    def mask(self) -> jnp.ndarray:
        cap = self.keys[0].shape[0]
        return jnp.arange(cap, dtype=jnp.int32) < self.n_groups


jax.tree_util.register_pytree_node(
    GroupResult,
    lambda g: ((g.keys, g.aggs, g.n_groups), tuple(sorted(g.aggs))),
    lambda aux, ch: GroupResult(keys=ch[0], aggs=ch[1], n_groups=ch[2]),
)


def groupby_aggregate(
    keys: Sequence[jnp.ndarray],
    values: Optional[Dict[str, Tuple[jnp.ndarray, str]]] = None,
    n_valid: Optional[jnp.ndarray] = None,
    count_name: Optional[str] = "count",
    valid_mask: Optional[jnp.ndarray] = None,
) -> GroupResult:
    """``df.groupby(keys).agg(values)`` — sort + segment-reduce.

    Args:
      keys: group-by key columns (equal static length).
      values: mapping output name -> (value column, agg) with agg in
        ``{"sum","count","max","min","mean"}``.
      n_valid: live-row count (defaults to capacity).
      count_name: if set, always emit a group-size aggregate under this name.
      valid_mask: arbitrary boolean live-row mask (overrides ``n_valid``).
    """
    keys = [jnp.asarray(k) for k in keys]
    cap = keys[0].shape[0]
    if valid_mask is not None:
        n_valid = jnp.sum(valid_mask).astype(jnp.int32)
    else:
        n_valid = jnp.asarray(cap if n_valid is None else n_valid, jnp.int32)
    values = dict(values or {})
    for name, (_, agg) in values.items():
        if agg not in _AGGS:
            raise ValueError(f"unknown agg {agg!r} for {name!r}")

    payloads = [v for v, _ in values.values()]
    skeys, spayloads = multi_key_sort(
        keys, payloads, n_valid=n_valid, valid_mask=valid_mask
    )
    seg, first, n_groups = segment_ids_from_sorted(skeys, n_valid)
    valid = jnp.arange(cap, dtype=jnp.int32) < n_valid

    out_keys = tuple(_scatter_firsts(k, seg, first, cap) for k in skeys)
    aggs: Dict[str, jnp.ndarray] = {}
    counts = None
    if count_name is not None or any(
        a in ("mean", "count") for _, a in values.values()
    ):
        counts = jax.ops.segment_sum(
            valid.astype(jnp.int32), seg, num_segments=cap + 1
        )[:cap]
    if count_name is not None:
        aggs[count_name] = counts

    for (name, (_, agg)), col in zip(values.items(), spayloads):
        if agg in ("sum", "mean"):
            s = jax.ops.segment_sum(
                jnp.where(valid, col, jnp.zeros((), col.dtype)),
                seg,
                num_segments=cap + 1,
            )[:cap]
            if agg == "sum":
                aggs[name] = s
            else:
                aggs[name] = s / jnp.maximum(counts, 1).astype(
                    s.dtype if jnp.issubdtype(s.dtype, jnp.floating) else jnp.float32
                )
        elif agg == "count":
            aggs[name] = counts  # group size — identical to the shared count
        elif agg == "max":
            ident = _min_ident(col.dtype)
            aggs[name] = jax.ops.segment_max(
                jnp.where(valid, col, ident), seg, num_segments=cap + 1
            )[:cap]
        elif agg == "min":
            ident = _max_ident(col.dtype)
            aggs[name] = jax.ops.segment_min(
                jnp.where(valid, col, ident), seg, num_segments=cap + 1
            )[:cap]
    return GroupResult(keys=out_keys, aggs=aggs, n_groups=n_groups)


def _min_ident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def _max_ident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


@dataclasses.dataclass(frozen=True)
class UniqueResult:
    """Sorted distinct values, their multiplicities, and the live count."""

    values: jnp.ndarray
    counts: jnp.ndarray
    weight_sums: Optional[jnp.ndarray]
    n_unique: jnp.ndarray  # scalar int32

    def mask(self) -> jnp.ndarray:
        cap = self.values.shape[0]
        return jnp.arange(cap, dtype=jnp.int32) < self.n_unique


jax.tree_util.register_pytree_node(
    UniqueResult,
    lambda u: ((u.values, u.counts, u.weight_sums, u.n_unique), None),
    lambda _, ch: UniqueResult(*ch),
)


def unique(
    x: jnp.ndarray,
    n_valid: Optional[jnp.ndarray] = None,
    weights: Optional[jnp.ndarray] = None,
    valid_mask: Optional[jnp.ndarray] = None,
) -> UniqueResult:
    """``pd.unique`` / ``np.unique(return_counts=True)`` with static shapes."""
    values = {"w": (weights, "sum")} if weights is not None else None
    g = groupby_aggregate(
        [x], values, n_valid=n_valid, count_name="count", valid_mask=valid_mask
    )
    return UniqueResult(
        values=g.keys[0],
        counts=g.aggs["count"],
        weight_sums=g.aggs.get("w"),
        n_unique=g.n_groups,
    )


def value_counts(
    x: jnp.ndarray, n_valid: Optional[jnp.ndarray] = None
) -> UniqueResult:
    """``df[col].value_counts()`` (unsorted-by-count; use counts + mask)."""
    return unique(x, n_valid=n_valid)


def drop_duplicates(
    keys: Sequence[jnp.ndarray], n_valid: Optional[jnp.ndarray] = None
) -> GroupResult:
    """``df[cols].drop_duplicates()`` — distinct key rows."""
    return groupby_aggregate(keys, None, n_valid=n_valid, count_name="count")


def factorize(
    x: jnp.ndarray,
    sorted_uniques: jnp.ndarray,
) -> jnp.ndarray:
    """Map each element of ``x`` to its rank in ``sorted_uniques``.

    ``sorted_uniques`` is the (tail-padded, ascending) output of ``unique``;
    padding slots hold values >= every live value only if the live max is the
    dtype max, in which case ``side='left'`` still lands on the first (live)
    occurrence — see tests/test_core_ops.py::test_factorize_dtype_max.
    """
    return jnp.searchsorted(sorted_uniques, x, side="left").astype(jnp.int32)


def masked_max(values: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Max over the masked entries with a zero floor.

    The suite-wide convention for tail-padded aggregate buffers: the
    statistics are non-negative counts/sums, so an all-masked buffer
    reports 0 (not the dtype min).  Shared by the scalar queries, the
    windowed suites and the distributed merge — one definition, one
    empty-input rule.
    """
    return jnp.max(jnp.where(mask, values, 0))


def clamp_k(k: int, capacity: int) -> int:
    """``min(k, capacity)`` — the static top-k clamp.

    ``lax.top_k`` rejects k > buffer length, so every top-k entry point
    clamps identically; centralising it keeps the output shapes of the
    plan/naive paths in step.
    """
    return min(k, capacity)


# -----------------------------------------------------------------------------
# Membership / semi-join / top-k (the end-to-end pipeline's extra vocabulary)
# -----------------------------------------------------------------------------

def isin(
    x: jnp.ndarray,
    sorted_uniques: jnp.ndarray,
    n_uniques: jnp.ndarray,
    n_valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """``df[col].isin(values)`` — single-key set membership.

    cuDF probes a hash table; with ``sorted_uniques`` already the tail-padded
    ascending output of :func:`unique`, the static-shape equivalent is one
    binary search per element (cheaper than re-hashing — the build cost was
    paid by the sort that produced the uniques).  Returns a (capacity,) bool
    mask, False on padding rows.
    """
    cap = x.shape[0]
    n_valid = jnp.asarray(cap if n_valid is None else n_valid, jnp.int32)
    pos = jnp.searchsorted(sorted_uniques, x, side="left").astype(jnp.int32)
    safe = jnp.minimum(pos, sorted_uniques.shape[0] - 1)
    hit = (pos < jnp.asarray(n_uniques, jnp.int32)) & (sorted_uniques[safe] == x)
    return hit & (jnp.arange(cap, dtype=jnp.int32) < n_valid)


def semi_join(
    left_keys: Sequence[jnp.ndarray],
    right_keys: Sequence[jnp.ndarray],
    left_n_valid: Optional[jnp.ndarray] = None,
    right_n_valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Multi-key semi-join membership: does left row i appear in right?

    The ETL op is ``df.merge(other, how="leftsemi")`` / hash-based
    set-membership; the static-shape formulation is the engine's usual
    sort-merge (DESIGN.md §2): concatenate both sides with a side flag,
    stable-sort by the keys, and mark every equal-key *run* that contains at
    least one right row.  One sort of ``L + R`` rows, no hash table.

    Returns a (left_capacity,) bool mask (False on left padding rows).
    """
    left_keys = [jnp.asarray(k) for k in left_keys]
    right_keys = [jnp.asarray(k) for k in right_keys]
    lcap = left_keys[0].shape[0]
    rcap = right_keys[0].shape[0]
    l_nv = jnp.asarray(lcap if left_n_valid is None else left_n_valid, jnp.int32)
    r_nv = jnp.asarray(rcap if right_n_valid is None else right_n_valid, jnp.int32)

    both = [jnp.concatenate([l, r]) for l, r in zip(left_keys, right_keys)]
    is_left = jnp.concatenate(
        [jnp.ones((lcap,), jnp.int32), jnp.zeros((rcap,), jnp.int32)]
    )
    idx = jnp.concatenate(
        [jnp.arange(lcap, dtype=jnp.int32), jnp.full((rcap,), lcap, jnp.int32)]
    )
    pos = jnp.arange(lcap + rcap, dtype=jnp.int32)
    valid = jnp.where(pos < lcap, pos < l_nv, pos - lcap < r_nv)

    skeys_and_side, (s_idx,) = multi_key_sort(
        [*both, is_left], [idx], valid_mask=valid
    )
    *skeys, s_is_left = skeys_and_side
    n_total = l_nv + r_nv
    seg, _, _ = segment_ids_from_sorted(list(skeys), n_total)
    # a run is "hit" iff it contains a right row (side flag 0 -> min == 0)
    run_min_side = jax.ops.segment_min(
        jnp.where(pos < n_total, s_is_left, 1), seg,
        num_segments=lcap + rcap + 1,
    )
    member = (run_min_side[seg] == 0) & (s_is_left == 1) & (pos < n_total)
    out = jnp.zeros((lcap + 1,), jnp.bool_)
    out = out.at[jnp.where(member, s_idx, lcap)].set(member)
    return out[:lcap]


def top_k(
    values: jnp.ndarray,
    k: int,
    valid_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Largest ``k`` live entries of ``values``: ``(vals, indices, n_live)``.

    ``df.nlargest(k)`` over a tail-padded column.  Ties break toward the
    lowest index (= lexicographically first group when ``values`` is a
    GroupResult aggregate, since group keys are emitted sorted).  Slots past
    ``n_live = min(k, #valid)`` hold the dtype min and index 0.  ``k`` is
    clamped to the buffer capacity (lax.top_k rejects k > len).
    """
    k = clamp_k(k, values.shape[0])
    masked = values if valid_mask is None else jnp.where(
        valid_mask, values, _min_ident(values.dtype)
    )
    vals, idx = lax.top_k(masked, k)
    n_live = jnp.asarray(
        values.shape[0] if valid_mask is None else jnp.sum(valid_mask), jnp.int32
    )
    n_live = jnp.minimum(n_live, k)
    keep = jnp.arange(k, dtype=jnp.int32) < n_live
    return (
        jnp.where(keep, vals, _min_ident(values.dtype)),
        jnp.where(keep, idx, 0).astype(jnp.int32),
        n_live,
    )


def argmax_top_k(
    values: jnp.ndarray,
    k: int,
    valid_mask: Optional[jnp.ndarray] = None,
    *,
    n_valid=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-free :func:`top_k`: ``k`` rounds of masked argmax.

    ``lax.top_k`` lowers to a full-length sort on CPU/XLA, which would spoil
    the sort-once query plan's HLO budget (DESIGN.md §2.3); for the small
    static ``k`` of the challenge report an O(k*n) argmax loop emits no sort
    op and returns the identical ``(vals, indices, n_live)`` triple —
    argmax's first-max tie rule matches top_k's lowest-index rule, and
    selected slots are retired to the dtype min.  Caveat: live values equal
    to the dtype min are indistinguishable from retired slots, so this
    variant requires ``values > dtype min`` on live rows (always true for
    the non-negative counts/packet sums it is used on).

    ``n_valid`` is a caller-known count of live rows: when the mask is
    already retired *into* ``values`` (the kernel lane's fused
    ``valid_mask``/``retire`` epilogue), pass ``n_valid`` instead of
    ``valid_mask`` and the ``sum(valid_mask)`` recount is skipped.
    """
    k = clamp_k(k, values.shape[0])
    masked = values if valid_mask is None else jnp.where(
        valid_mask, values, _min_ident(values.dtype)
    )
    ident = _min_ident(values.dtype)

    def body(i, carry):
        cur, vals, idx = carry
        j = jnp.argmax(cur).astype(jnp.int32)
        vals = vals.at[i].set(cur[j])
        idx = idx.at[i].set(j)
        return cur.at[j].set(ident), vals, idx

    _, vals, idx = lax.fori_loop(
        0, k, body,
        (masked, jnp.full((k,), ident, values.dtype), jnp.zeros((k,), jnp.int32)),
    )
    if n_valid is not None:
        n_live = jnp.asarray(n_valid, jnp.int32)
    else:
        n_live = jnp.asarray(
            values.shape[0] if valid_mask is None else jnp.sum(valid_mask),
            jnp.int32,
        )
    n_live = jnp.minimum(n_live, k)
    keep = jnp.arange(k, dtype=jnp.int32) < n_live
    return (
        jnp.where(keep, vals, ident),
        jnp.where(keep, idx, 0),
        n_live,
    )


# -----------------------------------------------------------------------------
# Permutations (anonymization substrate)
# -----------------------------------------------------------------------------

def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Murmur3-style finalizer — a bijection on uint32 (int32-safe wrapper)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def random_permutation(key: jax.Array, capacity: int, n_valid) -> jnp.ndarray:
    """Uniform random permutation of ``[0, n_valid)`` in a static buffer.

    The paper uses ``cupy.random.shuffle`` on an iota; the JAX equivalent with
    a *traced* ``n_valid`` is: draw random sort keys, push the invalid tail to
    the end with the validity key, and scatter ranks.  ``out[i]`` (i < n_valid)
    is the anonymized id of rank i, uniform over [0, n_valid); tail entries map
    into [n_valid, capacity) and must be ignored.
    """
    n_valid = jnp.asarray(n_valid, jnp.int32)
    r = jax.random.bits(key, (capacity,), dtype=jnp.uint32)
    (_,), (ranks,) = multi_key_sort([r], [jnp.arange(capacity, dtype=jnp.int32)], n_valid=n_valid)
    # ranks[j] = original rank that lands in slot j  (j < n_valid is random)
    out = jnp.zeros((capacity,), jnp.int32).at[ranks].set(
        jnp.arange(capacity, dtype=jnp.int32)
    )
    return out


def hash_permutation(capacity: int, n_valid, salt: int = 0x9E3779B9) -> jnp.ndarray:
    """Deterministic HashGraph-style permutation (Green et al. [22,23]).

    Sorting ranks by a bijective integer mix is the TPU analogue of deriving a
    permutation from hash-table insertion order: deterministic (supports the
    paper's 'deterministic testing' point), no RNG state, one sort.
    """
    n_valid = jnp.asarray(n_valid, jnp.int32)
    r = mix32(jnp.arange(capacity, dtype=jnp.uint32) + jnp.uint32(salt))
    (_,), (ranks,) = multi_key_sort([r], [jnp.arange(capacity, dtype=jnp.int32)], n_valid=n_valid)
    out = jnp.zeros((capacity,), jnp.int32).at[ranks].set(
        jnp.arange(capacity, dtype=jnp.int32)
    )
    return out
