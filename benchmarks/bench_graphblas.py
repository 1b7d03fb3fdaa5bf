"""Paper Fig. 2: jaxdf vs a GraphBLAS-style sparse-matrix reference,
plus the in-repo GraphBLAS-lite CSR A/B (DESIGN.md §2.4).

The challenge's verification path formulates every query over the traffic
matrix A_t in sparse linear algebra.  scipy.sparse.csr_matrix plays the
SuiteSparse-GraphBLAS role here (same formulation: 1^T A 1, |A|_0, A·1,
|A|_0·1, max(...)), giving the paper's "data science vs GraphBLAS"
comparison on identical hardware.  Since PR 5 the repo speaks that matrix
language natively (``core/sparse.py``), so this section also runs the
head-to-head the ISSUE gates on:

  * ``run_all_queries`` (group-by form) vs ``run_all_queries_csr`` (CSR
    reductions) — equality-asserted, both 3-sort;
  * the windowed suite, dense-grid vs CSR-scan formulation —
    equality-asserted, with the compiled-HLO peak-buffer estimate
    (``launch/hloanalysis.peak_buffer_bytes``) of the full ``analyze``
    program under each method: the O(n_windows × capacity) vs O(nnz)
    memory claim, measured.

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

from repro.challenge.pipeline import analyze_peak_buffer_bytes
from repro.core import Table, run_all_queries, run_all_queries_csr
from repro.core.temporal import windowed_queries

from .common import device_kind, emit, packet_arrays, run_manifest, time_fn

# the memory A/B compiles analyze twice; a larger window axis makes the
# dense grids' O(n_windows × capacity) term dominate (tests pin >= 4x here)
MEMORY_AB_WINDOWS = 32


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
    jcsr = jax.jit(run_all_queries_csr)
    t_jax = time_fn(jall, t, iters=iters)
    t_csr = time_fn(jcsr, t, iters=iters)
    t_gb = time_fn(lambda: graphblas_all_queries(src, dst, n_vertices), iters=iters)

    res, res_csr = jall(t), jcsr(t)
    ref = graphblas_all_queries(src, dst, n_vertices)
    ok = all(int(getattr(res, k)) == v for k, v in ref.items())
    ok_csr = all(int(getattr(res_csr, k)) == v for k, v in ref.items())
    if not (ok and ok_csr):
        raise AssertionError(
            f"scalar suite diverges from scipy-CSR reference "
            f"(groupby ok={ok}, csr ok={ok_csr})"
        )
    record("jaxdf_all14", t_jax, f"vs_scipy_csr={t_gb / t_jax:.2f}x correct={ok} n={n}")
    record("csr_all14", t_csr,
           f"matrix-language form, {t_jax / t_csr:.2f}x of groupby form "
           f"correct={ok_csr} n={n}")
    record("scipy_csr_all14", t_gb, f"n={n} reference")

    # ---- windowed suite: dense-grid vs CSR-scan A/B (equality-asserted) ----
    nw = 16
    rng = np.random.default_rng(0)
    ts = jnp.asarray(np.sort(rng.integers(0, 1 << 20, n)).astype(np.int32))
    tw = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst),
                          "ts": ts})
    wlen = (1 << 20) // nw
    jw_csr = jax.jit(lambda t: windowed_queries(t, wlen, nw, method="csr"))
    jw_grid = jax.jit(lambda t: windowed_queries(t, wlen, nw, method="grid"))
    t_wcsr = time_fn(jw_csr, tw, iters=iters)
    t_wgrid = time_fn(jw_grid, tw, iters=iters)
    a, b = jw_csr(tw), jw_grid(tw)
    for k in a:
        if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
            raise AssertionError(f"windowed csr/grid mismatch on {k}")
    record("windowed_csr", t_wcsr, f"{nw} windows, O(nnz) memory n={n}")
    record("windowed_grid", t_wgrid,
           f"dense baseline, csr={t_wgrid / t_wcsr:.2f}x of grid wall n={n}")

    # ---- peak-HBM A/B of the full analyze program (compile-only; shared
    # harness with tests/test_memory_budget.py) -----------------------------
    mem_n = min(n, 1 << 17)
    pk_csr = analyze_peak_buffer_bytes(
        mem_n, windowed_method="csr", n_windows=MEMORY_AB_WINDOWS)
    pk_grid = analyze_peak_buffer_bytes(
        mem_n, windowed_method="grid", n_windows=MEMORY_AB_WINDOWS)
    emit("graphblas/analyze_peak_bytes", 0.0,
         f"csr={pk_csr / 1e6:.1f}MB grid={pk_grid / 1e6:.1f}MB "
         f"ratio={pk_grid / pk_csr:.2f}x at n={mem_n} nw={MEMORY_AB_WINDOWS}")
    rows["analyze_peak_bytes"] = {
        "us_per_call": 0.0,
        "csr_peak_bytes": pk_csr,
        "grid_peak_bytes": pk_grid,
        "grid_over_csr": pk_grid / pk_csr,
        "n": float(mem_n),
        "n_windows": float(MEMORY_AB_WINDOWS),
    }

    # ---- roofline: both scalar-suite programs + the windowed CSR scan,
    # each against the already-measured steady wall of its own compiled
    # program (launch/roofline.program_roofline, ROADMAP item 5) ----
    from repro.launch.roofline import program_roofline

    roofline = {
        "csr_all14": program_roofline(
            jcsr.lower(t).compile().as_text(), t_csr, device_kind()),
        "jaxdf_all14": program_roofline(
            jall.lower(t).compile().as_text(), t_jax, device_kind()),
        "windowed_csr": program_roofline(
            jw_csr.lower(tw).compile().as_text(), t_wcsr, device_kind()),
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
