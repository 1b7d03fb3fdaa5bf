"""Graph-algorithm lane: the iterative tier (DESIGN.md §2.5) over the
anonymized traffic CSR — BFS, connected components, PageRank, triangles.

Each algorithm is timed as a jitted fixed-point program over the plan's
CSR pair and *verified against its NumPy oracle in the same run* — a
benchmark row here is also a correctness gate (``oracle_ok`` per row,
hard AssertionError on divergence).  The final row compiles the
challenge's graph phase (``graph_plan`` and every ``graph_kernel``) and
counts HLO sorts: the phase may sort only for its plan pair (the
algorithms are scatter/gather/segmented-reduce only), and a pass that runs
the queries too sorts no more than ``analyze`` alone, whose plan pair the
graph phase reuses.

The edge count is capped at 2^16 (noted in the derived column when it
bites): triangle counting's blocked A ⊙ (A·A) scan is O(row_capacity ×
(nnz + n_vertices)) — an algorithm-complexity lane, not a packet-
throughput lane.

Rows are written machine-readably to ``BENCH_algorithms.json`` when a
path is given, joining the ``BENCH_*.json`` trajectory family of
``benchmarks/run.py``.

    PYTHONPATH=src python -m benchmarks.bench_algorithms [--n N] [--json P]
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Table,
    bfs_levels,
    connected_components,
    count_hlo_sorts,
    pagerank,
    table_csrs,
    triangle_counts,
)
from repro.kernels.ref import ref_bfs, ref_cc, ref_pagerank, ref_triangles

from .common import emit, packet_arrays, time_fn

# triangle counting is O(row_capacity * (nnz + n_vertices)); cap the lane
# so the scan stays seconds, not minutes (reported, never silent)
MAX_EDGES = 1 << 16
SORT_BUDGET = 2  # the plan pair of the graph phase
PASS_SORT_BUDGET = 3  # analyze's, which the graph phase then adds none to


def run(
    n: int = 1 << 16, iters: int = 3, json_path: Optional[str] = None
) -> Dict[str, Dict[str, float]]:
    rows: Dict[str, Dict[str, float]] = {}

    def record(name, seconds, derived="", **extra):
        emit(f"algorithms/{name}", seconds, derived)
        rows[name] = {"us_per_call": seconds * 1e6, **extra}

    n_eff = min(n, MAX_EDGES)
    capped = f" (capped from n={n})" if n_eff < n else ""
    src_raw, dst_raw = packet_arrays(n_eff)
    # compact vertex domain: the anonymized-id regime the challenge runs in
    uniq = np.unique(np.concatenate([src_raw, dst_raw]))
    src = np.searchsorted(uniq, src_raw).astype(np.int32)
    dst = np.searchsorted(uniq, dst_raw).astype(np.int32)
    nv = len(uniq)
    t = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst)})
    csr_src, csr_dst = jax.jit(lambda t: table_csrs(t))(t)
    jax.block_until_ready((csr_src, csr_dst))

    jbfs = jax.jit(lambda a: bfs_levels(a, 0, nv))
    jcc = jax.jit(lambda a, b: connected_components(a, nv, csr_t=b))
    jpr = jax.jit(lambda a: pagerank(a, nv))
    jtri = jax.jit(lambda a: triangle_counts(a, nv))

    # ---- BFS ----
    t_bfs = time_fn(jbfs, csr_src, iters=iters)
    bfs = jbfs(csr_src)
    ok_bfs = np.array_equal(np.asarray(bfs.levels), ref_bfs(src, dst, nv, 0))
    record("bfs", t_bfs,
           f"{int(bfs.iterations)} iters, reached {int(bfs.n_reached)}/{nv}, "
           f"correct={ok_bfs} n={n_eff}{capped}",
           oracle_ok=float(ok_bfs), iterations=float(bfs.iterations))

    # ---- connected components ----
    t_cc = time_fn(jcc, csr_src, csr_dst, iters=iters)
    cc = jcc(csr_src, csr_dst)
    ok_cc = np.array_equal(np.asarray(cc.labels), ref_cc(src, dst, nv))
    record("components", t_cc,
           f"{int(cc.n_components)} components in {int(cc.iterations)} "
           f"iters, correct={ok_cc} n={n_eff}{capped}",
           oracle_ok=float(ok_cc), iterations=float(cc.iterations))

    # ---- PageRank ----
    t_pr = time_fn(jpr, csr_src, iters=iters)
    pr = jpr(csr_src)
    want, _, _ = ref_pagerank(src, dst, np.ones(n_eff), nv)
    l1 = float(np.abs(np.asarray(pr.ranks) - want).sum())
    ok_pr = l1 < 1e-6 and bool(pr.converged)
    record("pagerank", t_pr,
           f"{int(pr.iterations)} iters, oracle L1={l1:.2e}, "
           f"correct={ok_pr} n={n_eff}{capped}",
           oracle_ok=float(ok_pr), iterations=float(pr.iterations),
           oracle_l1=l1)

    # ---- triangles ----
    t_tri = time_fn(jtri, csr_src, iters=iters)
    tri = jtri(csr_src)
    want_pn, want_tot = ref_triangles(src, dst, nv)
    ok_tri = (int(tri.total) == want_tot and np.array_equal(
        np.asarray(tri.per_node), want_pn.astype(np.float32)))
    record("triangles", t_tri,
           f"{int(tri.total)} wedge closures, correct={ok_tri} "
           f"n={n_eff}{capped}",
           oracle_ok=float(ok_tri), total=float(tri.total))

    if not (ok_bfs and ok_cc and ok_pr and ok_tri):
        raise AssertionError(
            f"algorithm suite diverges from NumPy oracles (bfs={ok_bfs} "
            f"cc={ok_cc} pagerank={ok_pr} triangles={ok_tri})"
        )

    # ---- sort budget: the graph phase sorts only for its plan pair ----
    from repro.challenge.pipeline import (GRAPH_KERNELS, _analyze_and_plans,
                                          graph_kernel, graph_plan)

    cap = 1024
    tz = Table.from_dict(
        {c: np.zeros(cap, np.int32) for c in ("src", "dst", "win")},
        n_valid=cap - 1,
    )

    def phase(t, plans=None):
        g = graph_plan(t, cap, plans=plans)
        return [graph_kernel(g, kernel=k, n_vertices=2 * cap, backend="xla")
                for k in GRAPH_KERNELS]

    def whole_pass(t):
        results, plans = _analyze_and_plans(t, n_windows=4, ip_bins=64, k=5,
                                            backend="xla")
        return results, phase(t, plans)

    for name, fn, budget, what in (
            ("graph_sorts", phase, SORT_BUDGET, "the graph phase"),
            ("pass_sorts", whole_pass, PASS_SORT_BUDGET,
             "analyze and the graph phase")):
        sorts = count_hlo_sorts(jax.jit(fn).lower(tz).compile().as_text())
        emit(f"algorithms/{name}", 0.0,
             f"{what}: {sorts} HLO sorts (budget {budget})")
        rows[name] = {"us_per_call": 0.0, "hlo_sorts": float(sorts),
                      "budget": float(budget)}
        if sorts > budget:
            raise AssertionError(
                f"{what} lowered to {sorts} sorts (> budget {budget})")

    if json_path:
        payload = {"n": n_eff, "iters": iters,
                   "backend": jax.default_backend(), "rows": rows}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {json_path} ({len(rows)} rows)", flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=1 << 16)
    ap.add_argument("--quick", action="store_true", help="n = 2^13")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable rows (BENCH_algorithms.json)")
    args = ap.parse_args(argv)
    n = (1 << 13) if args.quick else args.n
    print("name,us_per_call,derived")
    run(n=n, iters=args.iters, json_path=args.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
