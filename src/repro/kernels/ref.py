"""Pure-jnp oracles for every Pallas kernel in this package — plus the
NumPy graph-algorithm oracles the iteration tier is locked against.

Each ``ref_*`` kernel oracle is the semantic ground truth the kernels are
sweep-tested against (tests/test_kernels.py, interpret=True on CPU).  The
graph oracles (``ref_bfs`` / ``ref_cc`` / ``ref_pagerank`` /
``ref_triangles``) are deliberately *boring* NumPy/SciPy — queues,
union-find, dense power iteration — structurally unlike the semiring
fixed-point versions in :mod:`repro.core.algorithms`, so agreement is
evidence (tests/test_algorithms.py; exact algorithms must match
bit-identically, PageRank to 1e-6 L1).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ref_histogram",
    "ref_segmented_reduce",
    "ref_segment_matmul",
    "ref_cms_update",
    "ref_hll_update",
    "ref_attention",
    "ref_bfs",
    "ref_cc",
    "ref_pagerank",
    "ref_triangles",
]


def ref_histogram(
    ids: jnp.ndarray,
    num_bins: int,
    weights: Optional[jnp.ndarray] = None,
    *,
    valid_mask: Optional[jnp.ndarray] = None,
    retire: float = 0.0,
) -> jnp.ndarray:
    """Weighted histogram: out[b] = sum_{i: ids[i]==b} weights[i].

    Out-of-range ids (e.g. the jaxdf padding id == capacity) are dropped.
    Fusion-epilogue semantics (the kernel contract, DESIGN.md §2.9):
    ``valid_mask`` (shape ``(num_bins,)``) overwrites masked-out bins with
    ``retire`` after the reduction.
    """
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    ok = (ids >= 0) & (ids < num_bins)
    out = jax.ops.segment_sum(
        jnp.where(ok, weights, 0).astype(jnp.float32),
        jnp.where(ok, ids, num_bins),
        num_segments=num_bins + 1,
    )[:num_bins]
    if valid_mask is not None:
        out = jnp.where(valid_mask, out, jnp.float32(retire))
    return out


def ref_segmented_reduce(
    vals: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    op: str = "sum",
    init: Optional[jnp.ndarray] = None,
    *,
    valid_mask: Optional[jnp.ndarray] = None,
    retire=None,
) -> jnp.ndarray:
    """1-D segmented reduction under a plus or max monoid (float32).

    ``out[s] = monoid-reduce over {vals[i] : seg_ids[i] == s}``, folded into
    ``init`` when given.  Out-of-range ids are dropped.  Empty segments
    yield the monoid identity: 0 for ``"sum"``, ``-inf`` for ``"max"`` —
    the GraphBLAS-lite reduction semantics of :mod:`repro.core.sparse`.

    Fusion-epilogue semantics (authoritative — the Pallas kernels are
    verified against this): ``valid_mask`` + ``retire`` overwrite
    masked-out segments LAST (after the ``init`` fold); ``retire`` defaults
    to the monoid identity.
    """
    ok = (seg_ids >= 0) & (seg_ids < num_segments)
    seg = jnp.where(ok, seg_ids, num_segments)
    if op == "sum":
        v = vals.astype(jnp.float32)
        out = jax.ops.segment_sum(
            jnp.where(ok, v, jnp.asarray(0, jnp.float32)), seg,
            num_segments=num_segments + 1,
        )[:num_segments]
        if init is not None:
            out = init.astype(jnp.float32) + out
        if valid_mask is not None:
            r = 0 if retire is None else retire
            out = jnp.where(valid_mask, out, jnp.asarray(r, jnp.float32))
        return out
    if op == "max":
        v = vals.astype(jnp.float32)
        out = jax.ops.segment_max(
            jnp.where(ok, v, -jnp.inf), seg, num_segments=num_segments + 1
        )[:num_segments]
        if init is not None:
            out = jnp.maximum(init.astype(jnp.float32), out)
        if valid_mask is not None:
            r = -jnp.inf if retire is None else retire
            out = jnp.where(valid_mask, out, jnp.float32(r))
        return out
    raise ValueError(f"unknown segmented-reduce op {op!r}")


def ref_cms_update(
    counts: jnp.ndarray,
    col_ids: jnp.ndarray,
    proposals: jnp.ndarray,
) -> jnp.ndarray:
    """Conservative-update CMS fold (oracle for kernels/sketch.py).

    ``out[r, c] = max(counts[r, c], max over i with col_ids[r, i] == c of
    proposals[i])`` — every depth row scatter-maxes the *same* proposal
    vector through its own hashed columns; cells nothing maps to keep their
    running value.  Out-of-range ids (incl. -1 = masked) are dropped.
    Works in ``counts.dtype`` (float32 or int32 — the sketch tier stores
    int32 so counts stay exact past 2^24).
    """
    depth, width = counts.shape
    dtype = counts.dtype
    sentinel = (jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
                else -jnp.inf)
    ids = col_ids.astype(jnp.int32)
    ok = (ids >= 0) & (ids < width)
    fused = jnp.where(
        ok,
        jnp.arange(depth, dtype=jnp.int32)[:, None] * width + ids,
        depth * width,
    )
    props = jnp.broadcast_to(
        proposals.astype(dtype)[None, :], ids.shape
    )
    upd = jax.ops.segment_max(
        jnp.where(ok, props, dtype.type(sentinel)).reshape(-1),
        fused.reshape(-1),
        num_segments=depth * width + 1,
    )[: depth * width].reshape(depth, width)
    return jnp.maximum(counts, upd)


def ref_hll_update(
    registers: jnp.ndarray,
    reg_ids: jnp.ndarray,
    rhos: jnp.ndarray,
) -> jnp.ndarray:
    """HyperLogLog register fold — segmented max with the running registers
    as the accumulator (oracle for kernels/sketch.hll_update_pallas)."""
    return ref_segmented_reduce(
        rhos.astype(jnp.float32), reg_ids, registers.shape[0], "max",
        init=registers,
    )


def ref_segment_matmul(
    x: jnp.ndarray, seg_ids: jnp.ndarray, num_segments: int
) -> jnp.ndarray:
    """Feature aggregation: out[s, :] = sum_{i: seg[i]==s} x[i, :]."""
    ok = (seg_ids >= 0) & (seg_ids < num_segments)
    return jax.ops.segment_sum(
        jnp.where(ok[:, None], x, 0),
        jnp.where(ok, seg_ids, num_segments),
        num_segments=num_segments + 1,
    )[:num_segments]


def ref_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Reference (G)QA attention.

    Shapes: q (B, Hq, Lq, D); k, v (B, Hkv, Lkv, D) with Hq % Hkv == 0.
    ``window``: sliding-window size (Mistral SWA) — query t attends to keys in
    (t - window, t].  Causal offsets assume Lq == Lkv or Lq == 1 (decode).
    """
    b, hq, lq, d = q.shape
    hkv, lkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)) * scale
    q_pos = jnp.arange(lq)[:, None] + (lkv - lq)  # align ends (decode: lq=1)
    k_pos = jnp.arange(lkv)[None, :]
    mask = jnp.ones((lq, lkv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    out = jax.nn.softmax(logits, axis=-1) @ vv.astype(jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# graph-algorithm oracles (NumPy/SciPy ground truth for core.algorithms)
# ---------------------------------------------------------------------------

def _ref_adjacency(
    src: np.ndarray, dst: np.ndarray, n_vertices: int
) -> Tuple[np.ndarray, np.ndarray]:
    """CSR-ish adjacency: (neighbors sorted by source, per-source offsets)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    order = np.argsort(src, kind="stable")
    starts = np.searchsorted(src[order], np.arange(n_vertices + 1))
    return dst[order], starts


def ref_bfs(
    src: np.ndarray, dst: np.ndarray, n_vertices: int, source: int
) -> np.ndarray:
    """Textbook queue BFS over directed edges: hop levels, -1 unreachable."""
    levels = np.full(n_vertices, -1, np.int32)
    if not 0 <= source < n_vertices:
        return levels
    nbrs, starts = _ref_adjacency(src, dst, n_vertices)
    levels[source] = 0
    frontier = [source]
    depth = 0
    while frontier:
        depth += 1
        nxt = []
        for u in frontier:
            for v in nbrs[starts[u]:starts[u + 1]]:
                if levels[v] < 0:
                    levels[v] = depth
                    nxt.append(int(v))
        frontier = nxt
    return levels


def ref_cc(src: np.ndarray, dst: np.ndarray, n_vertices: int) -> np.ndarray:
    """Weakly connected components by union-find: label = min vertex id in
    the component (isolated vertices are their own singletons)."""
    parent = np.arange(n_vertices, dtype=np.int64)

    def find(u):
        root = u
        while parent[root] != root:
            root = parent[root]
        while parent[u] != root:  # path compression
            parent[u], u = root, parent[u]
        return root

    for u, v in zip(np.asarray(src, np.int64), np.asarray(dst, np.int64)):
        ru, rv = find(u), find(v)
        if ru != rv:
            # union by min id keeps the root the component minimum
            lo, hi = (ru, rv) if ru < rv else (rv, ru)
            parent[hi] = lo
    return np.array([find(u) for u in range(n_vertices)], np.int32)


def ref_pagerank(
    src: np.ndarray,
    dst: np.ndarray,
    weights: np.ndarray,
    n_vertices: int,
    *,
    damping: float = 0.85,
    tol: float = 1e-6,
    max_iters: int = 100,
) -> Tuple[np.ndarray, int, bool]:
    """Dense float64 power iteration, same update as core.algorithms.pagerank.

    Duplicate (src, dst) rows act as additive weights (np.add.at), matching
    the duplicate-collapsing CSR build.  Returns (ranks, iterations,
    converged).
    """
    n = int(n_vertices)
    if n == 0:
        return np.zeros((0,), np.float64), 0, True
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(weights, np.float64)
    outw = np.zeros(n, np.float64)
    np.add.at(outw, src, w)
    r = np.full(n, 1.0 / n, np.float64)
    for it in range(1, max_iters + 1):
        contrib = np.divide(r, outw, out=np.zeros_like(r), where=outw > 0)
        y = np.zeros(n, np.float64)
        np.add.at(y, dst, w * contrib[src])
        dangling = r[outw <= 0].sum()
        new = damping * (y + dangling / n) + (1.0 - damping) / n
        residual = np.abs(new - r).sum()
        r = new
        if residual < tol:
            return r, it, True
    return r, max_iters, False


def ref_triangles(
    src: np.ndarray, dst: np.ndarray, n_vertices: int
) -> Tuple[np.ndarray, int]:
    """Masked sparse product C = A ⊙ (A·A) via SciPy (structural A).

    Returns (per-source-vertex wedge-closure counts, global total) — the
    oracle for core.algorithms.triangle_counts.
    """
    import scipy.sparse as sp

    n = int(n_vertices)
    a = sp.csr_matrix(
        (np.ones(len(src), np.float64),
         (np.asarray(src, np.int64), np.asarray(dst, np.int64))),
        shape=(n, n),
    )
    a.data[:] = 1.0  # collapse duplicate edges to structural 1s
    c = a.multiply(a @ a)
    per_node = np.asarray(c.sum(axis=1)).ravel()
    return per_node, int(round(c.sum()))
