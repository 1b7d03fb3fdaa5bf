"""Static-shape CSR traffic matrices — GraphBLAS-lite on the sort-once plan.

The paper frames the challenge as "GraphBLAS reinterpreted as data science":
every Table III query is a reduction over the sparse traffic matrix A_t.
The engine's windowed/streaming layers used to *densify* that matrix into
``(n_windows + 1, capacity + 1)`` scatter grids, paying O(windows × capacity)
memory for an overwhelmingly sparse object.  This module is the sparse-first
representation (DESIGN.md §2.4):

  * :class:`CsrMatrix` — compressed sparse rows in the repo's static-shape
    discipline: every buffer has a compile-time capacity, validity is the
    row-pointer prefix (``indptr[r] == nnz`` for every padding row), entry
    tails are padding.  Row identity is a *key tuple* (one array per key
    column), so the same type covers the batch traffic matrix (rows = src)
    and the stream's accumulated windowed matrix (rows = (win, src)).
  * :func:`csr_from_plan` — the zero-sort constructor.  A ``SortedEdges``
    plan already contains exactly the CSR's segment structure: the link
    segmentation is the entry list, the key0 segmentation is the row list,
    and the link ids at key0-group starts are the row pointers.  Building
    the CSR costs scatters only.
  * GraphBLAS-lite ops — ``reduce_rows``/``reduce_cols`` (plus/max
    monoids), ``degrees`` (|A|_0·1, a pointer difference), masked
    :func:`mxv`/:func:`vxm` through the Pallas segmented-reduction kernel
    (``kernels/ops.segmented_reduce``), :func:`ewise_union` for CSR↔CSR
    merge and duplicate-collapsing :func:`from_coo` (one packed sort).

Conventions: ``vals`` padding is 0 and key padding is the dtype max (so key
buffers stay globally sorted ascending, like every plan output); reductions
report 0 on empty/padding rows — the identity of the non-negative
count/packet-sum domain every challenge query lives in.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..kernels.ops import segmented_reduce
from .ops import (
    _max_ident,
    _min_ident,
    _scatter_firsts,
    multi_key_sort,
    segment_ids_from_sorted,
)
from .plan import SortedEdges

__all__ = [
    "CsrMatrix",
    "csr_from_plan",
    "from_coo",
    "ewise_union",
    "reduce_rows",
    "reduce_cols",
    "degrees",
    "mxv",
    "vxm",
    "transpose",
    "symmetrize",
    "gather_rows",
    "scatter_rows",
]


@dataclasses.dataclass(frozen=True)
class CsrMatrix:
    """Static-shape CSR: row pointers + column keys + values, tail-padded.

    ``row_keys`` is a tuple of ``(row_capacity,)`` arrays — the key columns
    identifying each row (padding = dtype max).  ``indptr`` has
    ``row_capacity + 1`` slots: ``indptr[r]`` is the first entry of row r
    for live rows and ``nnz`` for padding rows, so *validity is carried by
    the row-pointer prefix* — every padding row is empty by construction.
    ``col_keys``/``vals`` are the ``(nnz_capacity,)`` entry buffers (padding
    dtype max / 0).  ``n_rows``/``nnz`` are the live counts.
    """

    row_keys: Tuple[jnp.ndarray, ...]
    indptr: jnp.ndarray
    col_keys: jnp.ndarray
    vals: jnp.ndarray
    n_rows: jnp.ndarray  # scalar int32
    nnz: jnp.ndarray     # scalar int32

    @property
    def row_capacity(self) -> int:
        return self.row_keys[0].shape[0]

    @property
    def nnz_capacity(self) -> int:
        return self.col_keys.shape[0]

    def row_mask(self) -> jnp.ndarray:
        return jnp.arange(self.row_capacity, dtype=jnp.int32) < self.n_rows

    def entry_mask(self) -> jnp.ndarray:
        return jnp.arange(self.nnz_capacity, dtype=jnp.int32) < self.nnz

    def entry_rows(self) -> jnp.ndarray:
        """Row id of each stored entry (``row_capacity`` on padding slots).

        The inverse of the CSR compression (no stored per-entry row array):
        entry i belongs to row r iff ``indptr[r] <= i < indptr[r + 1]``, so
        its row is the number of row pointers at or below i, less one.  A 1
        scattered at each pointer below ``nnz`` and a prefix sum count them
        in O(row_capacity + nnz_capacity); the padding rows, all pointing
        at ``nnz``, scatter nothing.  Row pointers never decrease, so the
        scatter's indices come sorted and XLA adds no sort to order them.
        """
        cap = self.nnz_capacity
        idx = jnp.arange(cap, dtype=jnp.int32)
        starts = jnp.where(self.indptr < self.nnz, self.indptr, cap)
        marks = jnp.zeros((cap,), jnp.int32).at[starts].add(
            1, mode="drop", indices_are_sorted=True)
        rows = jnp.cumsum(marks, dtype=jnp.int32) - 1
        return jnp.where(idx < self.nnz, rows, self.row_capacity)

    def entry_row_key(
        self, k: int = 0, rows: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        """Expand row key column ``k`` back to per-entry granularity.

        ``rows`` lets a caller expanding several key columns reuse one
        :meth:`entry_rows` pass (eager execution repeats the scatter and
        prefix sum otherwise; under jit XLA CSE dedupes it either way).
        """
        key = self.row_keys[k]
        if rows is None:
            rows = self.entry_rows()
        safe = jnp.clip(rows, 0, self.row_capacity - 1)
        return jnp.where(
            self.entry_mask(), key[safe], _max_ident(key.dtype)
        )


jax.tree_util.register_dataclass(
    CsrMatrix,
    data_fields=[f.name for f in dataclasses.fields(CsrMatrix)],
    meta_fields=[],
)


def _resize(a: jnp.ndarray, size: int, fill) -> jnp.ndarray:
    if a.shape[0] == size:
        return a
    if a.shape[0] > size:
        return a[:size]
    return jnp.concatenate(
        [a, jnp.full((size - a.shape[0],), fill, a.dtype)]
    )


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------

def csr_from_plan(plan: SortedEdges) -> CsrMatrix:
    """The traffic matrix A_t as CSR, off an existing plan — ZERO sorts.

    The plan's link segmentation *is* the entry list (col = key1, val =
    per-link weight sum), its key0 segmentation *is* the row list, and the
    link id at each key0-group start *is* that row's pointer; everything
    here is adjacent-flag scatters over the already-sorted stream.
    """
    cap = plan.capacity
    valid = plan.valid_rows()
    col_keys = _scatter_firsts(plan.key1, plan.seg, plan.first, cap)
    vals = jax.ops.segment_sum(
        jnp.where(valid, plan.w, 0), plan.seg, num_segments=cap + 1
    )[:cap]
    row_keys = (_scatter_firsts(plan.key0, plan.k0_seg, plan.k0_first, cap),)
    # row pointer = link id at the first packet-row of each key0 group
    starts = (
        jnp.zeros((cap + 1,), jnp.int32)
        .at[jnp.where(plan.k0_first.astype(bool), plan.k0_seg, cap)]
        .set(plan.seg)
    )
    indptr = jnp.where(
        jnp.arange(cap + 1, dtype=jnp.int32) < plan.n_k0, starts, plan.n_links
    )
    return CsrMatrix(
        row_keys=row_keys, indptr=indptr, col_keys=col_keys, vals=vals,
        n_rows=plan.n_k0, nnz=plan.n_links,
    )


_COO_AGGS = ("plus", "max", "min")


def from_coo(
    row_keys: Sequence[jnp.ndarray],
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    n_valid: Optional[jnp.ndarray] = None,
    valid_mask: Optional[jnp.ndarray] = None,
    *,
    op: str = "plus",
    nnz_capacity: Optional[int] = None,
    row_capacity: Optional[int] = None,
) -> Tuple[CsrMatrix, jnp.ndarray]:
    """Duplicate-collapsing COO -> CSR: ONE sort by (row_keys..., cols).

    Duplicate (row, col) coordinates collapse under ``op`` (``"plus"`` /
    ``"max"`` / ``"min"`` — GraphBLAS ``GrB_Matrix_build`` dup semantics).
    With a 1-column row key the sort routes through the packed uint64 path.

    ``nnz_capacity`` (default: input capacity) bounds the output entries;
    excess *groups* — the lexicographically largest, a deterministic
    suffix — are dropped and **counted** in the returned ``dropped`` scalar
    (never silent, the repo-wide overflow contract).  ``row_capacity``
    (default ``nnz_capacity``) likewise bounds rows.

    Returns ``(csr, dropped)``.
    """
    if op not in _COO_AGGS:
        raise ValueError(f"unknown dup-collapse op {op!r}")
    row_keys = [jnp.asarray(k) for k in row_keys]
    cols = jnp.asarray(cols)
    vals = jnp.asarray(vals)
    cap_in = cols.shape[0]
    nnz_cap = cap_in if nnz_capacity is None else nnz_capacity
    row_cap = nnz_cap if row_capacity is None else row_capacity
    if valid_mask is not None:
        n_valid = jnp.sum(valid_mask).astype(jnp.int32)
    else:
        n_valid = jnp.asarray(cap_in if n_valid is None else n_valid, jnp.int32)

    skeys, (svals,) = multi_key_sort(
        [*row_keys, cols], [vals],
        n_valid=None if valid_mask is not None else n_valid,
        valid_mask=valid_mask,
    )
    *srow_keys, scols = skeys
    seg, first, n_groups = segment_ids_from_sorted(skeys, n_valid)
    r_seg, r_first, _ = segment_ids_from_sorted(srow_keys, n_valid)
    valid = jnp.arange(cap_in, dtype=jnp.int32) < n_valid

    # entry buffers at input granularity (group slot g = entry g)
    g_cols = _scatter_firsts(scols, seg, first, cap_in)
    if op == "plus":
        agg = jax.ops.segment_sum(
            jnp.where(valid, svals, 0), seg, num_segments=cap_in + 1
        )[:cap_in]
    elif op == "max":
        agg = jax.ops.segment_max(
            jnp.where(valid, svals, _min_ident(svals.dtype)), seg,
            num_segments=cap_in + 1,
        )[:cap_in]
    else:
        agg = jax.ops.segment_min(
            jnp.where(valid, svals, _max_ident(svals.dtype)), seg,
            num_segments=cap_in + 1,
        )[:cap_in]

    # row id of each entry (group), via the group-start scatter
    entry_row = (
        jnp.full((cap_in + 1,), row_cap, jnp.int32)
        .at[jnp.where(first.astype(bool), seg, cap_in)]
        .set(r_seg)
    )[:cap_in]
    # truncation: entries are lex-sorted, so both overflow cuts are suffix
    # cuts — keep the first n_kept groups, count the rest as dropped
    gidx = jnp.arange(cap_in, dtype=jnp.int32)
    fits_rows = jnp.sum((gidx < n_groups) & (entry_row < row_cap)).astype(jnp.int32)
    n_kept = jnp.minimum(jnp.minimum(n_groups, nnz_cap), fits_rows)
    dropped = (n_groups - n_kept).astype(jnp.int32)
    n_rows_kept = jnp.where(
        n_kept > 0, entry_row[jnp.maximum(n_kept - 1, 0)] + 1, 0
    ).astype(jnp.int32)

    e_live = jnp.arange(nnz_cap, dtype=jnp.int32) < n_kept
    col_keys = jnp.where(
        e_live, _resize(g_cols, nnz_cap, _max_ident(g_cols.dtype)),
        _max_ident(g_cols.dtype),
    )
    out_vals = jnp.where(
        e_live, _resize(agg, nnz_cap, jnp.zeros((), agg.dtype)),
        jnp.zeros((), agg.dtype),
    )

    r_live = jnp.arange(row_cap, dtype=jnp.int32) < n_rows_kept
    out_row_keys = []
    for k, sk in zip(row_keys, srow_keys):
        buf = _scatter_firsts(sk, r_seg, r_first, cap_in)
        out_row_keys.append(jnp.where(
            r_live, _resize(buf, row_cap, _max_ident(k.dtype)),
            _max_ident(k.dtype),
        ))

    # row pointer = entry id at the first packet-row of each row group
    starts = (
        jnp.zeros((cap_in + 1,), jnp.int32)
        .at[jnp.where(r_first.astype(bool), r_seg, cap_in)]
        .set(seg)
    )
    indptr = jnp.where(
        jnp.arange(row_cap + 1, dtype=jnp.int32) < n_rows_kept,
        jnp.minimum(_resize(starts, row_cap + 1, 0), n_kept),
        n_kept,
    )
    csr = CsrMatrix(
        row_keys=tuple(out_row_keys), indptr=indptr, col_keys=col_keys,
        vals=out_vals, n_rows=n_rows_kept, nnz=n_kept,
    )
    return csr, dropped


def ewise_union(
    a: CsrMatrix,
    b: CsrMatrix,
    *,
    op: str = "plus",
    nnz_capacity: Optional[int] = None,
    row_capacity: Optional[int] = None,
) -> Tuple[CsrMatrix, jnp.ndarray]:
    """CSR ↔ CSR element-wise union (GraphBLAS ``eWiseAdd``): entries
    present in either operand, coincident coordinates combined under
    ``op``.  One concat + one :func:`from_coo` sort — the engine's
    sort-based replacement for a hash-table upsert, and the streaming
    state's merge primitive.  Returns ``(csr, dropped)`` with overflow
    counted exactly like :func:`from_coo`.
    """
    if len(a.row_keys) != len(b.row_keys):
        raise ValueError(
            f"row-key arity mismatch: {len(a.row_keys)} vs {len(b.row_keys)}"
        )
    if nnz_capacity is None:
        nnz_capacity = max(a.nnz_capacity, b.nnz_capacity)
    if row_capacity is None:
        row_capacity = max(a.row_capacity, b.row_capacity)
    a_rows, b_rows = a.entry_rows(), b.entry_rows()
    rows = [
        jnp.concatenate([a.entry_row_key(i, a_rows), b.entry_row_key(i, b_rows)])
        for i in range(len(a.row_keys))
    ]
    return from_coo(
        rows,
        jnp.concatenate([a.col_keys, b.col_keys]),
        jnp.concatenate([a.vals, b.vals]),
        valid_mask=jnp.concatenate([a.entry_mask(), b.entry_mask()]),
        op=op,
        nnz_capacity=nnz_capacity,
        row_capacity=row_capacity,
    )


# ---------------------------------------------------------------------------
# GraphBLAS-lite reductions (exact integer paths)
# ---------------------------------------------------------------------------

def reduce_rows(csr: CsrMatrix, op: str = "plus") -> jnp.ndarray:
    """A·1 under a plus or max monoid: one value per row slot.

    Empty and padding rows report 0 — the identity of the non-negative
    count/packet domain (matching the zero-filled dense grids this
    representation replaces).  Exact integer arithmetic (no kernel
    dispatch); :func:`mxv` is the float semiring product path.
    """
    seg = csr.entry_rows()
    live = csr.entry_mask()
    cap = csr.row_capacity
    if op == "plus":
        return jax.ops.segment_sum(
            jnp.where(live, csr.vals, 0), seg, num_segments=cap + 1
        )[:cap]
    if op == "max":
        return jnp.maximum(jax.ops.segment_max(
            jnp.where(live, csr.vals, 0), seg, num_segments=cap + 1
        )[:cap], 0)
    raise ValueError(f"unknown monoid {op!r}")


def reduce_cols(
    csr: CsrMatrix, num_cols: int, op: str = "plus"
) -> jnp.ndarray:
    """1^T·A over a compact column domain: ``col_keys`` are the bins.

    Requires column keys in ``[0, num_cols)`` (the anonymized-id domain of
    the challenge tables); out-of-range entries are dropped.  Empty columns
    report 0, as in :func:`reduce_rows`.
    """
    ok = csr.entry_mask() & (csr.col_keys >= 0) & (csr.col_keys < num_cols)
    seg = jnp.where(ok, csr.col_keys.astype(jnp.int32), num_cols)
    if op == "plus":
        return jax.ops.segment_sum(
            jnp.where(ok, csr.vals, 0), seg, num_segments=num_cols + 1
        )[:num_cols]
    if op == "max":
        return jnp.maximum(jax.ops.segment_max(
            jnp.where(ok, csr.vals, 0), seg, num_segments=num_cols + 1
        )[:num_cols], 0)
    raise ValueError(f"unknown monoid {op!r}")


def degrees(csr: CsrMatrix) -> jnp.ndarray:
    """|A|_0·1 — stored entries per row.  A pointer difference: the CSR
    holds the fan-out/fan-in query for free (padding rows report 0)."""
    return (csr.indptr[1:] - csr.indptr[:-1]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# semiring mxv / vxm (Pallas segmented-reduction path)
# ---------------------------------------------------------------------------

_ADD_OPS = {"plus": "sum", "max": "max", "min": "max"}
_MUL_OPS = ("times", "first", "second")
_ADD_IDENTS = {"plus": 0.0, "max": -jnp.inf, "min": jnp.inf}


def _semiring_reduce(
    prod: jnp.ndarray, seg: jnp.ndarray, num_segments: int, add: str,
    backend: str, mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Dispatch the ⊕ reduction.  The min monoid rides the max kernel by
    negation (min(x) = -max(-x), identity ``+inf``) — no third kernel.

    ``mask`` rides the kernel's fused ``valid_mask``/``retire`` epilogue
    (DESIGN.md §2.9): masked-out segments take the ⊕ identity inside the
    reduction's final grid step instead of a separate ``where`` pass.  For
    min the retire value is negated along with everything else (``-inf``
    into the max kernel surfaces as ``+inf``).
    """
    if add == "min":
        return -segmented_reduce(
            -prod, seg, num_segments, op="max", backend=backend,
            valid_mask=mask, retire=None if mask is None else -_ADD_IDENTS["min"],
        )
    return segmented_reduce(
        prod, seg, num_segments, op=_ADD_OPS[add], backend=backend,
        valid_mask=mask, retire=None if mask is None else _ADD_IDENTS[add],
    )


def _products(
    vals: jnp.ndarray, xv: jnp.ndarray, mul: str
) -> jnp.ndarray:
    v = vals.astype(jnp.float32)
    if mul == "times":
        return v * xv
    if mul == "first":
        return v
    return xv  # "second"


def mxv(
    csr: CsrMatrix,
    x: jnp.ndarray,
    *,
    add: str = "plus",
    mul: str = "times",
    mask: Optional[jnp.ndarray] = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """Masked ``y = A ⊕.⊗ x`` over the (add, mul) semiring, float32.

    ``x`` is indexed by column key (compact-id domain, like
    :func:`reduce_cols`; entries with out-of-range columns drop out).
    ``mask`` (``(row_capacity,)`` bool) keeps only the selected output rows
    — GraphBLAS ``GrB_mxv`` with a structural mask; unmasked/empty rows
    report the ⊕ identity (0 for plus, ``-inf`` for max, ``+inf`` for min).
    The reduction dispatches through the Pallas segmented-reduction kernel
    (``kernels/ops.segmented_reduce``; min rides the max kernel by
    negation).
    """
    if add not in _ADD_OPS or mul not in _MUL_OPS:
        raise ValueError(f"unsupported semiring ({add!r}, {mul!r})")
    x = jnp.asarray(x)
    n_x = x.shape[0]
    ok = csr.entry_mask() & (csr.col_keys >= 0) & (csr.col_keys < n_x)
    safe = jnp.clip(csr.col_keys.astype(jnp.int32), 0, n_x - 1)
    prod = _products(csr.vals, x[safe].astype(jnp.float32), mul)
    seg = jnp.where(ok, csr.entry_rows(), -1)
    return _semiring_reduce(prod, seg, csr.row_capacity, add, backend, mask)


def vxm(
    x: jnp.ndarray,
    csr: CsrMatrix,
    num_cols: int,
    *,
    add: str = "plus",
    mul: str = "times",
    mask: Optional[jnp.ndarray] = None,
    backend: str = "auto",
    rows: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Masked ``y = x ⊕.⊗ A`` — the column-side mirror of :func:`mxv`.

    ``x`` is indexed by row slot (length ``row_capacity``); the output has
    ``num_cols`` slots indexed by column key.  Same semiring/mask/identity
    conventions and kernel dispatch as :func:`mxv`.  ``rows`` is the index
    into ``x`` of each entry: its row slot, ``csr.entry_rows()`` (the
    default), or, for an ``x`` over the row keys' domain, the entry's row
    key, ``csr.entry_row_key(0)`` (which folds :func:`gather_rows` into
    this one gather).  An iteration passes it in, computed once.
    """
    if add not in _ADD_OPS or mul not in _MUL_OPS:
        raise ValueError(f"unsupported semiring ({add!r}, {mul!r})")
    x = jnp.asarray(x)
    if rows is None:
        rows = csr.entry_rows()
    ok = (
        csr.entry_mask()
        & (csr.col_keys >= 0) & (csr.col_keys < num_cols)
        & (rows < x.shape[0])
    )
    safe = jnp.clip(rows, 0, x.shape[0] - 1)
    prod = _products(csr.vals, x[safe].astype(jnp.float32), mul)
    seg = jnp.where(ok, csr.col_keys.astype(jnp.int32), -1)
    return _semiring_reduce(prod, seg, num_cols, add, backend, mask)


# ---------------------------------------------------------------------------
# structural helpers (transpose / symmetrize / vertex <-> row-slot bridges)
# ---------------------------------------------------------------------------

def transpose(
    csr: CsrMatrix,
    *,
    nnz_capacity: Optional[int] = None,
    row_capacity: Optional[int] = None,
) -> Tuple[CsrMatrix, jnp.ndarray]:
    """A^T for a single-key-column CSR: ONE :func:`from_coo` sort.

    Swaps the roles of row key and column key (rows of the result are the
    distinct column keys of ``csr``).  Entries are already distinct, so
    with the default capacities (``nnz_capacity`` entries can never need
    more than ``nnz_capacity`` rows) nothing can drop; ``dropped`` is
    returned anyway to honour the counted-overflow contract when a caller
    shrinks the capacities.  Returns ``(csr_t, dropped)``.
    """
    if len(csr.row_keys) != 1:
        raise ValueError(
            f"transpose needs a 1-column row key, got {len(csr.row_keys)}"
        )
    if nnz_capacity is None:
        nnz_capacity = csr.nnz_capacity
    return from_coo(
        [csr.col_keys],
        csr.entry_row_key(0),
        csr.vals,
        valid_mask=csr.entry_mask(),
        op="plus",
        nnz_capacity=nnz_capacity,
        row_capacity=row_capacity,
    )


def symmetrize(
    csr: CsrMatrix,
    csr_t: Optional[CsrMatrix] = None,
    *,
    op: str = "plus",
    nnz_capacity: Optional[int] = None,
    row_capacity: Optional[int] = None,
) -> Tuple[CsrMatrix, jnp.ndarray]:
    """A ⊕ A^T via :func:`ewise_union` — two sorts, or one when the caller
    already holds the transpose (e.g. the challenge's src/dst plan pair).

    Coincident (u, v)/(v, u) entries combine under ``op``; the default
    ``nnz_capacity`` doubles the input's so a fully asymmetric matrix still
    fits.  Returns ``(csr_sym, dropped)``.
    """
    if csr_t is None:
        csr_t, _ = transpose(csr)
    if nnz_capacity is None:
        nnz_capacity = csr.nnz_capacity + csr_t.nnz_capacity
    if row_capacity is None:
        row_capacity = nnz_capacity
    return ewise_union(
        csr, csr_t, op=op,
        nnz_capacity=nnz_capacity, row_capacity=row_capacity,
    )


def gather_rows(
    csr: CsrMatrix, x: jnp.ndarray, *, fill=0.0
) -> jnp.ndarray:
    """Row-slot view of a vertex-domain vector: ``out[r] = x[row_key[r]]``.

    The bridge from the vertex-indexed outputs of :func:`vxm` back to the
    row-slot inputs :func:`vxm` consumes — iterative algorithms alternate
    the two domains every step.  Rows whose key falls outside ``[0,
    len(x))`` — padding rows included (key = dtype max) — report ``fill``
    (pass the ⊕ identity of the surrounding semiring).
    """
    x = jnp.asarray(x)
    key = csr.row_keys[0].astype(jnp.int32)
    ok = csr.row_mask() & (key >= 0) & (key < x.shape[0])
    safe = jnp.clip(key, 0, x.shape[0] - 1)
    return jnp.where(ok, x[safe], jnp.asarray(fill, x.dtype))


def scatter_rows(
    csr: CsrMatrix, slot_vals: jnp.ndarray, num_vertices: int, *, fill=0.0
) -> jnp.ndarray:
    """Vertex-domain view of a row-slot vector: ``out[row_key[r]] =
    slot_vals[r]`` — the inverse bridge of :func:`gather_rows`.

    Row keys are distinct by construction, so the scatter has no
    collisions; vertices with no row (and keys outside ``[0,
    num_vertices)``) report ``fill``.
    """
    slot_vals = jnp.asarray(slot_vals)
    key = csr.row_keys[0].astype(jnp.int32)
    ok = csr.row_mask() & (key >= 0) & (key < num_vertices)
    out = jnp.full((num_vertices + 1,), fill, slot_vals.dtype)
    return out.at[jnp.where(ok, key, num_vertices)].set(slot_vals)[:num_vertices]
