"""Paper Fig. 2: jaxdf vs a GraphBLAS-style sparse-matrix reference.

The challenge's verification path formulates every query over the traffic
matrix A_t in sparse linear algebra.  scipy.sparse.csr_matrix plays the
SuiteSparse-GraphBLAS role here (same formulation: 1^T A 1, |A|_0, A·1,
|A|_0·1, max(...)), giving the paper's "data science vs GraphBLAS"
comparison on identical hardware, equality-asserted query by query.

Rows are written machine-readably to ``BENCH_graphblas.json`` when a path
is given — joining the ``BENCH_queries.json`` trajectory emitted by
``benchmarks/run.py``.

    PYTHONPATH=src python -m benchmarks.bench_graphblas [--n N] [--json P]
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from repro.core import Table, run_all_queries

from .common import device_kind, emit, packet_arrays, run_manifest, time_fn


def graphblas_all_queries(src, dst, n_vertices: int):
    """All Table III stats via sparse matrix ops (the reference role)."""
    data = np.ones(len(src), np.int64)
    A = sp.coo_matrix((data, (src, dst)), shape=(n_vertices, n_vertices)).tocsr()
    A.sum_duplicates()
    out_deg = np.asarray(A.sum(axis=1)).ravel()     # A·1
    in_deg = np.asarray(A.sum(axis=0)).ravel()      # 1^T·A
    fanout = np.diff(A.indptr)                      # |A|_0·1
    Ac = A.tocsc()
    fanin = np.diff(Ac.indptr)
    return {
        "valid_packets": int(A.sum()),
        "unique_links": int(A.nnz),
        "max_link_packets": int(A.data.max()) if A.nnz else 0,
        "n_unique_sources": int((out_deg > 0).sum()),
        "n_unique_destinations": int((in_deg > 0).sum()),
        "n_unique_ips": int(((out_deg > 0) | (in_deg > 0)).sum()),
        "max_source_packets": int(out_deg.max()),
        "max_source_fanout": int(fanout.max()),
        "max_destination_packets": int(in_deg.max()),
        "max_destination_fanin": int(fanin.max()),
    }


def run(
    n: int = 1 << 20, iters: int = 3, json_path: Optional[str] = None
) -> Dict[str, Dict[str, float]]:
    rows: Dict[str, Dict[str, float]] = {}

    def record(name, seconds, derived="", **extra):
        emit(f"graphblas/{name}", seconds, derived)
        rows[name] = {"us_per_call": seconds * 1e6, **extra}

    src, dst = packet_arrays(n)
    n_vertices = int(max(src.max(), dst.max())) + 1
    t = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst)})

    jall = jax.jit(run_all_queries)
    t_jax = time_fn(jall, t, iters=iters)
    t_gb = time_fn(lambda: graphblas_all_queries(src, dst, n_vertices), iters=iters)

    res = jall(t)
    ref = graphblas_all_queries(src, dst, n_vertices)
    ok = all(int(getattr(res, k)) == v for k, v in ref.items())
    if not ok:
        raise AssertionError("scalar suite diverges from scipy-CSR reference")
    record("jaxdf_all14", t_jax, f"vs_scipy_csr={t_gb / t_jax:.2f}x correct={ok} n={n}")
    record("scipy_csr_all14", t_gb, f"n={n} reference")

    # ---- roofline: the scalar-suite program against its already-measured
    # steady wall (launch/roofline.program_roofline, ROADMAP item 5) ----
    from repro.launch.roofline import program_roofline

    roofline = {
        "jaxdf_all14": program_roofline(
            jall.lower(t).compile().as_text(), t_jax, device_kind()),
    }
    for kname, rf in roofline.items():
        emit(f"roofline/{kname}", rf["wall_s"],
             f"{rf['roofline_fraction']:.4f} of peak "
             f"({rf['bottleneck']}-bound, "
             f"{rf['achieved_bytes_per_s'] / 1e9:.2f} GB/s)")

    if json_path:
        payload = {"n": n, "iters": iters,
                   "backend": jax.default_backend(), "rows": rows,
                   "roofline": roofline, "manifest": run_manifest()}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {json_path} ({len(rows)} rows)", flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--quick", action="store_true", help="n = 2^14")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable rows (BENCH_graphblas.json)")
    args = ap.parse_args(argv)
    n = (1 << 14) if args.quick else args.n
    print("name,us_per_call,derived")
    run(n=n, iters=args.iters, json_path=args.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
