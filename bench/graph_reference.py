"""Plain NumPy reference of the Graphalytics kernels BFS, WCC and PageRank.

Float64 NumPy that imports nothing of the program.  The graph is the
directed traffic graph of an anonymized capture: vertices are the
anonymized ids ``[0, n)``, one link per distinct (src, dst) pair
(duplicate packets collapse to one link; self-loops are kept).  The kernel
definitions follow the LDBC Graphalytics specification (arXiv 2011.15028):

- BFS: the hop level of every vertex from the source over out-links, -1
  where unreachable (Graphalytics writes int64 max there);
- WCC: each vertex labelled with the smallest id of its weak component
  (direction ignored, as Graphalytics does for directed graphs);
- PageRank: unweighted, rank split evenly over a vertex's distinct
  out-links, the rank of vertices with no out-link spread evenly over all
  ``n`` vertices, damping ``d``, run under its own stopping rule (an L1
  change below ``tol`` within ``max_steps`` steps).

:func:`checks` compares the program's outputs with these and gives each
compared number, which the cell's driver holds to :data:`LIMITS`.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: each compared number's limit: levels and labels exact, PageRank within
#: an L1 distance of the float64 reference and stopped within one step of
#: where the reference's own stopping rule stops, every kernel converged
LIMITS = {"bfs_wrong": 0, "wcc_wrong": 0, "pagerank_l1": 1e-4,
          "pagerank_steps_off": 1, "unconverged": 0}


def links(src, dst) -> Tuple[np.ndarray, np.ndarray]:
    """The distinct directed links (u, v) of the rows (src, dst)."""
    key = np.unique(np.asarray(src, np.int64) << 32
                    | np.asarray(dst, np.int64))
    return key >> 32, key & 0xFFFFFFFF


def _out_lists(u, v, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """(out-neighbours ordered by source, each vertex's first position)."""
    order = np.argsort(u, kind="stable")
    return v[order], np.searchsorted(u[order], np.arange(n + 1))


def bfs_levels(u, v, n: int, source: int) -> np.ndarray:
    """Hop levels from ``source`` over the links (u -> v), level by level;
    -1 where unreachable."""
    nbrs, starts = _out_lists(u, v, n)
    levels = np.full(n, -1, np.int64)
    levels[source] = 0
    frontier = np.array([source], np.int64)
    depth = 0
    while frontier.size:
        depth += 1
        lo, hi = starts[frontier], starts[frontier + 1]
        counts = hi - lo
        # the out-lists of the whole frontier, concatenated
        pos = np.repeat(lo - np.cumsum(counts) + counts, counts) \
            + np.arange(counts.sum())
        reached = np.unique(nbrs[pos])
        frontier = reached[levels[reached] < 0]
        levels[frontier] = depth
    return levels


def wcc_labels(u, v, n: int, carrier=np.float64) -> np.ndarray:
    """The smallest vertex id of each vertex's weak component: labels start
    as the vertices' own ids, held in ``carrier``, and each step gives every
    vertex the smallest label among itself and its neighbours either way,
    until nothing changes.  Returns the labels as float64."""
    labels = np.arange(n).astype(carrier)
    ends = np.concatenate([u, v])
    other = np.concatenate([v, u])
    order = np.argsort(ends, kind="stable")
    ends, other = ends[order], other[order]
    first = np.flatnonzero(np.r_[True, ends[1:] != ends[:-1]])
    owners = ends[first]
    while True:
        new = labels.copy()
        new[owners] = np.minimum(labels[owners],
                                 np.minimum.reduceat(labels[other], first))
        if np.array_equal(new, labels):
            return labels.astype(np.float64)
        labels = new


def pagerank(u, v, n: int, steps: int, *, damping: float, tol: float = 0.0,
             carrier=np.asarray) -> Tuple[np.ndarray, int]:
    """Unweighted PageRank from the uniform vector, every rank vector
    rounded by ``carrier`` (float64 by default): ``steps`` steps, or fewer
    where a step's L1 change falls below ``tol``.  Returns (ranks, steps
    taken)."""
    outdeg = np.bincount(u, minlength=n).astype(np.float64)
    has_out = outdeg > 0
    r = carrier(np.full(n, 1.0 / n))
    for step in range(1, steps + 1):
        contrib = carrier(np.divide(r, outdeg, out=np.zeros(n),
                                    where=has_out))
        y = np.bincount(v, weights=contrib[u], minlength=n)
        new = carrier(damping * (y + r[~has_out].sum() / n)
                      + (1 - damping) / n)
        residual = np.abs(new - r).sum()
        r = new
        if residual < tol:
            return r, step
    return r, steps


def round_bf16(x) -> np.ndarray:
    """``x`` rounded to bfloat16 (nearest, ties to even), as float64."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


def _padded_wrong(got, want, fill) -> int:
    """Slots where ``got`` differs from ``want`` on ``[0, n)``, or from
    ``fill`` past it (float64 holds every id exactly)."""
    got = np.asarray(got, np.float64)
    n = len(want)
    if got.shape[0] < n:
        return n
    return int(np.count_nonzero(got[:n] != want)
               + np.count_nonzero(got[n:] != fill))


def checks(got: Dict[str, object], u, v, n: int, source: int, *,
           damping: float, tol: float, max_steps: int) -> Dict[str, float]:
    """Each compared number of one pass's graph outputs against the
    reference over the links (u, v) of ``n`` vertices.

    ``got`` holds whichever of ``bfs_levels``, ``wcc_labels`` and
    ``pagerank_ranks`` (with ``pagerank_steps``, the steps the program
    took) the pass produced, over the program's vertex domain (every slot
    from ``n`` on reads -1, or rank 0), and ``unconverged``.  PageRank is
    compared with the reference run under its own stopping rule (``tol``,
    ``max_steps``): the ranks by their L1 distance, the step count by how
    far it lies from the reference's."""
    out = {}
    keep = (u < n) & (v < n)  # ids past n: the anonymization check fails
    u, v = u[keep], v[keep]
    if "bfs_levels" in got:
        out["bfs_wrong"] = (_padded_wrong(got["bfs_levels"],
                                          bfs_levels(u, v, n, source), -1)
                            if 0 <= source < n else n)
    if "wcc_labels" in got:
        out["wcc_wrong"] = _padded_wrong(got["wcc_labels"],
                                         wcc_labels(u, v, n), -1)
    if "pagerank_ranks" in got:
        want, steps = pagerank(u, v, n, max_steps, damping=damping, tol=tol)
        ranks = np.asarray(got["pagerank_ranks"], np.float64)
        out["pagerank_l1"] = (float(np.abs(ranks[:n] - want).sum()
                                    + np.abs(ranks[n:]).sum())
                              if ranks.shape[0] >= n else float("inf"))
        out["pagerank_steps_off"] = abs(int(got["pagerank_steps"]) - steps)
    out["unconverged"] = int(got.get("unconverged", 0))
    return out
