"""Iterative graph algorithms on the CSR substrate (DESIGN.md §2.5).

The Graph Challenge lineage the paper sits in — *Static Graph Challenge*
triangle counting, GraphBLAST-style direction-optimized semiring iteration
— is exactly the workload the GraphBLAS-lite layer of
:mod:`repro.core.sparse` exists for.  This module adds the iteration tier:
a fixed-point harness and, on top of it, BFS levels, connected components,
PageRank, and triangle counting, all over the anonymized traffic CSR that
:func:`repro.core.sparse.csr_from_plan` builds from the sort-once plan.

Conventions shared by every algorithm here:

  * **Vertex domain.**  The graph's vertices are the compact anonymized-id
    range ``[0, n_live)`` held in static ``(n_vertices,)`` buffers
    (``n_vertices`` is a compile-time capacity, ``n_live`` a runtime
    scalar).  Iteration state lives in this domain; one step is a masked
    :func:`~repro.core.sparse.vxm` push (``y[v] = ⊕_u A[u, v] ⊗ x[u]``)
    with :func:`~repro.core.sparse.gather_rows` bridging vertex-indexed
    state back to the row-slot inputs ``vxm`` consumes; an iteration
    folds the two into one gather through each entry's row key, computed
    once before its loop (``entry_row_key``).  Everything is
    scatters, gathers, and segmented reductions — **zero sorts** beyond
    whatever plan the CSR came from (asserted by the challenge HLO budget
    tests).
  * **Fixed points, never silent cap-outs.**  Every loop runs through
    :func:`fixed_point`: a ``lax.while_loop`` with a *static* iteration cap
    whose result carries the executed iteration count **and** a
    ``converged`` flag — hitting the cap returns the well-formed partial
    state with ``converged == False``, it never masquerades as an answer.
  * **Frontier push.**  BFS and connected components push only the
    vertices that changed in the previous step.  A step whose active set
    is small (at most ``frontier[0]`` vertices owning at most
    ``frontier[1]`` stored entries) runs over just those entries; any
    larger step runs the full masked ``vxm``.  A full product costs one
    gather and one scatter over every stored entry, whatever the active
    set, so without this the near-empty tail levels of a BFS cost as much
    as its widest level.
  * **float32 carriers.**  Distances, labels, and wedge counts ride float32
    through the semiring kernels, exact for integers below 2**24: a vertex
    domain past 2**24 ids would make the labels silently wrong, so the
    challenge's graph phase refuses one
    (``repro.challenge.pipeline.graph_vertices``).
  * **Oracle-locked.**  Each algorithm has a NumPy twin in
    :mod:`repro.kernels.ref` (``ref_bfs`` / ``ref_cc`` / ``ref_pagerank``
    / ``ref_triangles``); the exact algorithms must match bit-identically,
    PageRank to 1e-6 L1 (tests/test_algorithms.py, scales 10 and 14).
    The benchmark holds the challenge's graph phase to its own NumPy
    reference (``bench/graph_reference.py``) at the timed sizes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.ops import segmented_reduce
from .sparse import (
    CsrMatrix,
    reduce_rows,
    scatter_rows,
    vxm,
)

__all__ = [
    "FRONTIER",
    "FixedPoint",
    "fixed_point",
    "UNREACHABLE",
    "BfsResult",
    "bfs_levels",
    "ComponentsResult",
    "connected_components",
    "PageRankResult",
    "pagerank",
    "TriangleResult",
    "triangle_counts",
    "AlgorithmResults",
    "graph_algorithms",
]

_INF = jnp.float32(jnp.inf)

#: BFS level / component label reported for unreachable or non-live
#: vertices — a sentinel, never garbage.
UNREACHABLE = -1

#: (vertices, entries): the largest active set a BFS or components step
#: pushes over its own entries instead of the full product (the vertices
#: capped by the vertex domain, the entries by the CSR's; ``(0, 0)``
#: always runs the full product).  On the RMAT traffic graph at 2^22
#: links this takes the near-empty tail levels off the full product and
#: leaves it the wide middle ones, whose number barely moves with the
#: capture (PERF.md)
FRONTIER = (1 << 12, 1 << 17)


# ---------------------------------------------------------------------------
# frontier push — the (min, second) product over the active vertices only
# ---------------------------------------------------------------------------

def _compact(active: jnp.ndarray, cap: int):
    """The ids of the first ``cap`` active vertices (``len(active)`` past
    their count) and their count: a prefix count and a binary search per
    slot, no scatter over the vertex domain."""
    count = jnp.cumsum(active.astype(jnp.int32))
    ids = jnp.searchsorted(count, jnp.arange(1, cap + 1, dtype=jnp.int32),
                           side="left").astype(jnp.int32)
    return ids, count[-1]


def _push_min(
    x: jnp.ndarray,
    active: jnp.ndarray,
    compact,
    csr: CsrMatrix,
    n: int,
    *,
    live: jnp.ndarray,
    rows: jnp.ndarray,
    backend: str,
    frontier: Tuple[int, int],
) -> jnp.ndarray:
    """``y[v] = min x[u]`` over the stored entries ``u -> v`` with ``u``
    active (``+inf`` where none; non-live ``v`` read ``+inf``): the masked
    ``vxm(where(active, x, inf), csr, add="min", mul="second")``.

    ``compact`` is :func:`_compact` of ``active`` at ``frontier[0]``.  When
    the active vertices fit it and own at most ``frontier[1]`` entries, the
    product runs over just those entries (each vertex's row found by a
    binary search over the row keys); otherwise over all of them.  Both
    give the same ``y``: a min over the same entries."""
    n_front, n_entries = frontier
    n_entries = min(n_entries, csr.nnz_capacity)

    def dense(_):
        return vxm(jnp.where(active, x, _INF), csr, n, add="min",
                   mul="second", mask=live, backend=backend, rows=rows)

    if n_front <= 0 or n_entries <= 0:
        return dense(None)
    ids, count = compact
    rk = csr.row_keys[0]
    cap_r = csr.row_capacity
    r = jnp.searchsorted(rk, ids.astype(rk.dtype), side="left")
    r = jnp.minimum(r.astype(jnp.int32), cap_r - 1)
    has = (ids < n) & (r < csr.n_rows) & (rk[r] == ids.astype(rk.dtype))
    start = csr.indptr[r]
    deg = jnp.where(has, csr.indptr[r + 1] - start, 0)
    ends = jnp.cumsum(deg)
    fits = (count <= n_front) & (ends[-1] <= n_entries)

    def sparse(_):
        offs = ends - deg
        k = jnp.arange(n_entries, dtype=jnp.int32)
        # the active vertex owning each of the first ends[-1] slots: its
        # index marked where its entries start, carried forward
        owner = jax.lax.cummax(
            jnp.zeros((n_entries,), jnp.int32).at[
                jnp.where(deg > 0, offs, n_entries)
            ].max(jnp.arange(ids.shape[0], dtype=jnp.int32), mode="drop"))
        ok = k < ends[-1]
        pos = jnp.where(ok, start[owner] + (k - offs[owner]), 0)
        cols = csr.col_keys[pos].astype(jnp.int32)
        ok = ok & (cols >= 0) & (cols < n)
        vals = x[jnp.minimum(ids, n - 1)][owner]
        y = jnp.full((n,), _INF, jnp.float32).at[
            jnp.where(ok, cols, n)].min(jnp.where(ok, vals, _INF), mode="drop")
        return jnp.where(live, y, _INF)

    return jax.lax.cond(fits, sparse, dense, None)


# ---------------------------------------------------------------------------
# fixed-point harness
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FixedPoint:
    """Result of :func:`fixed_point`: final state + how the loop ended.

    ``iterations`` is the number of ``step`` applications actually
    executed; ``converged`` is True iff the convergence test passed (False
    means the static cap was hit first — the state is still well-formed,
    just not a fixed point).
    """

    state: Any
    iterations: jnp.ndarray  # scalar int32
    converged: jnp.ndarray   # scalar bool


jax.tree_util.register_dataclass(
    FixedPoint,
    data_fields=[f.name for f in dataclasses.fields(FixedPoint)],
    meta_fields=[],
)


def fixed_point(
    step: Callable[[Any], Any],
    init: Any,
    max_iters: int,
    converged: Callable[[Any, Any], jnp.ndarray],
) -> FixedPoint:
    """Iterate ``state = step(state)`` to a fixed point — ``lax.while_loop``
    with a static cap and an explicit convergence verdict.

    ``converged(old, new) -> bool scalar`` is evaluated after every step;
    the loop stops as soon as it holds or after ``max_iters`` steps
    (``max_iters`` is static — the loop-carried shapes never change, so
    the whole iteration jits to one ``while`` op).  The repo-wide overflow
    contract applies to iteration budgets too: capping out is *reported*
    via ``converged == False``, never silently passed off as convergence.

    ``init`` may be any pytree; the state threads through untouched, so
    the harness works for scalars, vectors, and (dist, frontier)-style
    tuples alike.
    """
    if max_iters < 0:
        raise ValueError(f"max_iters must be >= 0, got {max_iters}")

    def cond(carry):
        _, it, conv = carry
        return jnp.logical_not(conv) & (it < max_iters)

    def body(carry):
        old, it, _ = carry
        new = step(old)
        verdict = jnp.asarray(converged(old, new), bool).reshape(())
        return new, it + jnp.int32(1), verdict

    state, iterations, conv = jax.lax.while_loop(
        cond, body,
        (init, jnp.zeros((), jnp.int32), jnp.zeros((), bool)),
    )
    return FixedPoint(state=state, iterations=iterations, converged=conv)


# ---------------------------------------------------------------------------
# BFS levels — min-plus frontier expansion
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BfsResult:
    """Hop levels from a source over directed edges.

    ``levels[v]`` is the minimum hop count source -> v, ``UNREACHABLE``
    (-1) for unreachable and non-live vertices.  ``iterations`` equals
    eccentricity(source) + 1 when converged (the +1 is the empty-frontier
    confirmation pass).
    """

    levels: jnp.ndarray     # (n_vertices,) int32
    n_reached: jnp.ndarray  # scalar int32
    iterations: jnp.ndarray
    converged: jnp.ndarray


jax.tree_util.register_dataclass(
    BfsResult,
    data_fields=[f.name for f in dataclasses.fields(BfsResult)],
    meta_fields=[],
)


def bfs_levels(
    csr: CsrMatrix,
    source,
    n_vertices: int,
    *,
    n_live=None,
    max_iters: Optional[int] = None,
    backend: str = "auto",
    row_keys: Optional[jnp.ndarray] = None,
    frontier: Tuple[int, int] = FRONTIER,
) -> BfsResult:
    """BFS hop levels from ``source`` — min-plus masked frontier expansion.

    Each step pushes the frontier's distances one hop through the (min,
    second) semiring: ``cand = vxm(dist | frontier, A) + 1`` (``second``
    skips the ⊗ multiply entirely — packet weights carry no distance and
    ``inf * 0`` NaNs are never formed), then ``dist = min(dist, cand)``;
    the frontier is exactly the vertices whose distance improved, and the
    fixed point is the empty frontier.  A frontier within ``frontier``
    (vertices, entries) pushes over its own entries only (:data:`FRONTIER`).
    ``max_iters`` defaults to ``n_vertices`` (the longest possible shortest
    path + confirmation).  ``row_keys`` is ``csr.entry_row_key(0)``,
    computed here once when not given.
    """
    n = int(n_vertices)
    keys = csr.entry_row_key(0) if row_keys is None else row_keys
    cap = n if max_iters is None else max_iters
    n_live_ = jnp.asarray(n if n_live is None else n_live, jnp.int32)
    vids = jnp.arange(n, dtype=jnp.int32)
    live = vids < n_live_
    source = jnp.asarray(source, jnp.int32)
    n_front = min(frontier[0], n)

    dist0 = jnp.full((n,), _INF, jnp.float32).at[source].set(0.0)
    front0 = (vids == source) & live

    def step(carry):
        dist, front = carry
        compact = _compact(front, n_front) if n_front > 0 else None
        hop = _push_min(
            dist, front, compact, csr, n, live=live, rows=keys,
            backend=backend, frontier=(n_front, frontier[1]),
        ) + 1.0
        new = jnp.minimum(dist, hop)
        return new, new < dist

    fp = fixed_point(
        step, (dist0, front0), cap,
        lambda old, new: jnp.logical_not(jnp.any(new[1])),
    )
    dist, _ = fp.state
    reached = live & jnp.isfinite(dist)
    levels = jnp.where(reached, dist, jnp.float32(UNREACHABLE)).astype(jnp.int32)
    return BfsResult(
        levels=levels,
        n_reached=jnp.sum(reached).astype(jnp.int32),
        iterations=fp.iterations,
        converged=fp.converged,
    )


# ---------------------------------------------------------------------------
# connected components — min-label propagation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ComponentsResult:
    """Weakly connected components as min-vertex-id labels.

    ``labels[v]`` is the smallest vertex id in v's component
    (``UNREACHABLE`` on non-live vertices); ``n_components`` counts label
    roots (``labels[v] == v``) over the live range — isolated live
    vertices are singleton components.
    """

    labels: jnp.ndarray        # (n_vertices,) int32
    n_components: jnp.ndarray  # scalar int32
    iterations: jnp.ndarray
    converged: jnp.ndarray


jax.tree_util.register_dataclass(
    ComponentsResult,
    data_fields=[f.name for f in dataclasses.fields(ComponentsResult)],
    meta_fields=[],
)


def connected_components(
    csr: CsrMatrix,
    n_vertices: int,
    *,
    csr_t: Optional[CsrMatrix] = None,
    n_live=None,
    max_iters: Optional[int] = None,
    backend: str = "auto",
    row_keys: Optional[jnp.ndarray] = None,
    row_keys_t: Optional[jnp.ndarray] = None,
    frontier: Tuple[int, int] = FRONTIER,
) -> ComponentsResult:
    """Label propagation under the (min, second) semiring to a fixed point.

    Labels start as own vertex ids, but for the vertex with the most
    out-links, which starts below them all (-1); each step pushes the
    labels that changed in the previous step over both edge directions
    (``A`` and ``A^T``) and keeps the min — weak connectivity without
    materializing ``A ⊕ A^T``: pass the challenge's dst-keyed CSR as
    ``csr_t`` and the whole computation adds **zero** sorts to the plan's
    budget (``csr_t=None`` falls back to one
    :func:`~repro.core.sparse.transpose` sort).  Each component settles on
    its smallest starting label, and the hub's then takes its smallest id,
    so the labels are the min-id labels whichever vertex started low.
    Starting from the hub makes the step count its eccentricity (+1 to
    confirm) rather than that of whichever vertex holds the smallest id,
    which moves with the anonymization key; a changed set within
    ``frontier`` pushes over its own entries only (:data:`FRONTIER`).
    Converges in at most diameter+1 steps (cap: ``n_vertices``).
    ``row_keys``/``row_keys_t`` are the two CSRs' ``entry_row_key(0)``,
    computed here once when not given.
    """
    n = int(n_vertices)
    cap = n if max_iters is None else max_iters
    n_live_ = jnp.asarray(n if n_live is None else n_live, jnp.int32)
    vids = jnp.arange(n, dtype=jnp.int32)
    live = vids < n_live_
    if csr_t is None:
        from .sparse import transpose  # local: keep the zero-sort path lean

        csr_t, _ = transpose(csr)
    keys = csr.entry_row_key(0) if row_keys is None else row_keys
    keys_t = csr_t.entry_row_key(0) if row_keys_t is None else row_keys_t
    n_front = min(frontier[0], n)

    # the row with the most entries (padding rows hold none)
    hub = csr.row_keys[0][jnp.argmax(csr.indptr[1:] - csr.indptr[:-1])]
    hub = jnp.where((csr.nnz > 0) & (hub >= 0) & (hub < n), hub, n)
    labels0 = jnp.where(live, vids.astype(jnp.float32), _INF)
    labels0 = jnp.where(live & (vids == hub), -1.0, labels0)

    def step(carry):
        labels, changed = carry
        compact = _compact(changed, n_front) if n_front > 0 else None
        kw = dict(live=live, backend=backend,
                  frontier=(n_front, frontier[1]))
        fwd = _push_min(labels, changed, compact, csr, n, rows=keys, **kw)
        bwd = _push_min(labels, changed, compact, csr_t, n, rows=keys_t, **kw)
        new = jnp.minimum(labels, jnp.minimum(fwd, bwd))
        return new, new < labels

    fp = fixed_point(
        step, (labels0, live), cap,
        lambda old, new: jnp.logical_not(jnp.any(new[1])),
    )
    low = fp.state[0] < 0  # the hub's component
    labels = jnp.where(low, jnp.min(jnp.where(low, vids, n)), fp.state[0])
    labels = jnp.where(live, labels, jnp.float32(UNREACHABLE)).astype(jnp.int32)
    roots = live & (labels == vids)
    return ComponentsResult(
        labels=labels,
        n_components=jnp.sum(roots).astype(jnp.int32),
        iterations=fp.iterations,
        converged=fp.converged,
    )


# ---------------------------------------------------------------------------
# PageRank — damped plus-times vxm with L1-residual convergence
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PageRankResult:
    """Damped PageRank over the traffic graph.

    ``ranks`` sums to 1 over the live range (0 on non-live slots; dangling
    mass is redistributed uniformly, so mass is conserved every step).
    ``residual`` is the L1 change of the final step.
    """

    ranks: jnp.ndarray     # (n_vertices,) float32
    residual: jnp.ndarray  # scalar float32
    iterations: jnp.ndarray
    converged: jnp.ndarray


jax.tree_util.register_dataclass(
    PageRankResult,
    data_fields=[f.name for f in dataclasses.fields(PageRankResult)],
    meta_fields=[],
)


def pagerank(
    csr: CsrMatrix,
    n_vertices: int,
    *,
    n_live=None,
    damping: float = 0.85,
    tol: float = 1e-6,
    max_iters: int = 100,
    weighted: bool = True,
    backend: str = "auto",
    row_keys: Optional[jnp.ndarray] = None,
) -> PageRankResult:
    """Power iteration ``r = d·(rP + dangling/n) + (1-d)/n`` to L1 tol.

    ``weighted=True`` (the traffic-graph default) splits each vertex's
    rank over its out-edges proportionally to packet counts (the (plus,
    times) semiring against ``contrib = r / out_weight``);
    ``weighted=False`` splits uniformly over out-degree.  Dangling
    vertices (no out-edges) teleport their mass uniformly across the live
    range, so ``sum(ranks) == 1`` to float32 roundoff at every step.
    Damping contracts the iteration by ``d`` per step, so the L1 residual
    test bounds the distance to the true fixed point by ``tol/(1-d)``.
    ``row_keys`` is ``csr.entry_row_key(0)``, computed here once when not
    given.
    """
    n = int(n_vertices)
    keys = csr.entry_row_key(0) if row_keys is None else row_keys
    n_live_ = jnp.asarray(n if n_live is None else n_live, jnp.int32)
    live = jnp.arange(n, dtype=jnp.int32) < n_live_
    nf = jnp.maximum(n_live_, 1).astype(jnp.float32)
    d = jnp.float32(damping)

    w_slot = reduce_rows(csr, "plus").astype(jnp.float32)
    if not weighted:
        from .sparse import degrees

        w_slot = degrees(csr).astype(jnp.float32)
    outw = scatter_rows(csr, w_slot, n, fill=0.0)
    base = jnp.where(live, 1.0 / nf, 0.0)  # uniform over live vertices
    mul = "times" if weighted else "second"

    def step(carry):
        r, _ = carry
        has_out = outw > 0
        contrib = jnp.where(has_out, r / jnp.where(has_out, outw, 1.0), 0.0)
        y = vxm(contrib, csr, n, add="plus", mul=mul, mask=live,
                backend=backend, rows=keys)
        dangling = jnp.sum(jnp.where(live & ~has_out, r, 0.0))
        new = d * (y + dangling * base) + (1.0 - d) * base
        return new, jnp.sum(jnp.abs(new - r))

    fp = fixed_point(
        step, (base, _INF), max_iters,
        lambda old, new: new[1] < jnp.float32(tol),
    )
    ranks, residual = fp.state
    return PageRankResult(
        ranks=ranks,
        residual=residual,
        iterations=fp.iterations,
        converged=fp.converged,
    )


# ---------------------------------------------------------------------------
# triangle counting — masked sparse A ⊙ (A·A)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TriangleResult:
    """Masked sparse-matrix triangle census ``C = A ⊙ (A·A)`` (structural).

    ``per_entry[e]`` counts the length-2 directed paths closing edge e
    (``C[i, j] = |{k : A[i,k] ∧ A[k,j]}|`` for stored (i, j));
    ``per_node`` sums per source vertex, ``total`` over the graph.  On a
    symmetric simple graph ``total == 6 ×`` the undirected triangle count
    (each triangle closes 6 ordered edge-wedge pairs).
    """

    per_entry: jnp.ndarray  # (nnz_capacity,) float32
    per_node: jnp.ndarray   # (n_vertices,) float32
    total: jnp.ndarray      # scalar int32


jax.tree_util.register_dataclass(
    TriangleResult,
    data_fields=[f.name for f in dataclasses.fields(TriangleResult)],
    meta_fields=[],
)


def triangle_counts(
    csr: CsrMatrix,
    n_vertices: int,
    *,
    block: int = 64,
    backend: str = "auto",
) -> TriangleResult:
    """Structural ``A ⊙ (A·A)`` without materializing A·A — zero sorts.

    The mask ⊙ means only the ``nnz`` stored coordinates of ``A`` are ever
    evaluated, so the product stays at entry granularity: a ``lax.scan``
    over row-slot blocks of size ``block`` densifies one (block ×
    n_vertices) slice of A at a time and accumulates, per stored entry
    (i, j), the wedge count ``Σ_k A[i, k]·A[k, j]`` restricted to middle
    vertices k owned by the block.  Per-node counts then roll up through
    the segmented-reduction kernel (``kernels/ops.segmented_reduce``) with
    the entry→row-vertex expansion as segment ids.  O(row_capacity ×
    (nnz + n_vertices)) work in ``row_capacity / block`` scan steps, each
    in O(block × n_vertices) memory — the static-shape discipline's
    answer to a data-dependent sparse-sparse product.
    """
    n = int(n_vertices)
    blk = int(block)
    cap_r, cap_e = csr.row_capacity, csr.nnz_capacity
    live_e = csr.entry_mask()
    rows_e = csr.entry_rows()                     # cap_r on padding slots
    cols_e = csr.col_keys.astype(jnp.int32)
    col_ok = live_e & (cols_e >= 0) & (cols_e < n)
    col_safe = jnp.clip(cols_e, 0, n - 1)

    # exact row slot owning vertex col_keys[e] (cap_r = "no such row");
    # searchsorted alone ranks — the equality check makes it a lookup
    rk = csr.row_keys[0]
    pos = jnp.searchsorted(rk, csr.col_keys, side="left").astype(jnp.int32)
    pos_safe = jnp.minimum(pos, cap_r - 1)
    hit = (pos < csr.n_rows) & (rk[pos_safe] == csr.col_keys) & live_e
    c_slot = jnp.where(hit, pos_safe, cap_r)

    steps = max(1, -(-cap_r // blk))

    def body(acc, k0):
        in_k = live_e & (rows_e >= k0) & (rows_e < k0 + blk)
        # dk[b, j] = A[slot k0+b, j] structural (one dense block slice)
        dk = (
            jnp.zeros((blk + 1, n + 1), jnp.float32)
            .at[
                jnp.where(in_k & col_ok, rows_e - k0, blk),
                jnp.where(in_k & col_ok, col_safe, n),
            ]
            .set(1.0)[:blk, :n]
        )
        # dc[r, b] = A[slot r, key(slot k0+b)] — entries whose column is a
        # row key owned by this block, scattered by (own row, block offset)
        in_c = (c_slot >= k0) & (c_slot < k0 + blk)
        dc = (
            jnp.zeros((cap_r + 1, blk + 1), jnp.float32)
            .at[
                jnp.where(in_c, rows_e, cap_r),
                jnp.where(in_c, c_slot - k0, blk),
            ]
            .set(1.0)[:cap_r, :blk]
        )
        left = dc[jnp.minimum(rows_e, cap_r - 1)]   # (cap_e, blk): A[i_e, k_b]
        right = dk.T[col_safe]                      # (cap_e, blk): A[k_b, j_e]
        contrib = jnp.sum(left * right, axis=1)
        return acc + jnp.where(col_ok, contrib, 0.0), None

    per_entry, _ = jax.lax.scan(
        body,
        jnp.zeros((cap_e,), jnp.float32),
        jnp.arange(steps, dtype=jnp.int32) * blk,
    )

    rvert = csr.entry_row_key(0, rows_e).astype(jnp.int32)
    seg = jnp.where(live_e & (rvert >= 0) & (rvert < n), rvert, -1)
    per_node = segmented_reduce(per_entry, seg, n, op="sum", backend=backend)
    return TriangleResult(
        per_entry=per_entry,
        per_node=per_node,
        total=jnp.sum(per_node).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# the bundle — all four off one plan pair
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AlgorithmResults:
    """All four Graph Challenge algorithms off one (A, A^T) CSR pair."""

    bfs: BfsResult
    components: ComponentsResult
    pagerank: PageRankResult
    triangles: TriangleResult


jax.tree_util.register_dataclass(
    AlgorithmResults,
    data_fields=[f.name for f in dataclasses.fields(AlgorithmResults)],
    meta_fields=[],
)


def graph_algorithms(
    csr_src: CsrMatrix,
    csr_dst: CsrMatrix,
    n_vertices: int,
    *,
    n_live=None,
    source=0,
    damping: float = 0.85,
    tol: float = 1e-6,
    pagerank_iters: int = 100,
    max_iters: Optional[int] = None,
    backend: str = "auto",
) -> AlgorithmResults:
    """Run BFS + components + PageRank + triangles off the plan's CSR pair.

    ``csr_src`` is the src-keyed traffic matrix A, ``csr_dst`` the
    dst-keyed A^T — the pair :func:`repro.core.queries.table_csrs` already
    builds from the two challenge plans, so the whole bundle adds **zero**
    sorts (components uses ``csr_dst`` as its transpose; nothing else
    needs one).
    """
    return AlgorithmResults(
        bfs=bfs_levels(
            csr_src, source, n_vertices,
            n_live=n_live, max_iters=max_iters, backend=backend,
        ),
        components=connected_components(
            csr_src, n_vertices,
            csr_t=csr_dst, n_live=n_live, max_iters=max_iters,
            backend=backend,
        ),
        pagerank=pagerank(
            csr_src, n_vertices,
            n_live=n_live, damping=damping, tol=tol,
            max_iters=pagerank_iters, backend=backend,
        ),
        triangles=triangle_counts(csr_src, n_vertices, backend=backend),
    )
