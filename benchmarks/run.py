"""Benchmark harness — one section per paper table/figure.

Emits ``name,us_per_call,derived`` CSV rows:
  io/*           paper Table II   (format read times)
  query/*        paper Fig. 1 + Table III (per-query speedups vs numpy)
  graphblas/*    paper Fig. 2     (vs scipy-CSR GraphBLAS-style reference)
  algorithms/*   Graph Challenge  (BFS/CC/PageRank/triangles, oracle-gated)
  anonymize/*    paper §IV        (shuffle vs HashGraph-style vs numpy)
  kernel/*       beyond-paper     (autotune sweep: chosen vs default config)
  distributed/*  beyond-paper     (shard_map suite over this process's devices)
  endtoend/*     paper pipeline   (per-phase + fused full-workload throughput)
  sketch/*       beyond-paper     (bounded-memory tier: wall + error-vs-bound)
  serve/*        beyond-paper     (fault-tolerant service: checkpoint tax +
                                   crash recovery, gated on bit-identity)

The query section always writes its rows machine-readably (steady-state
us/call + compiled-HLO sort counts per op) to ``--bench-json``
(default ``BENCH_queries.json``) — the bench trajectory file; ``--ab`` adds
the plan-vs-naive head-to-head rows (DESIGN.md §2.3).  The graphblas
section likewise writes ``--graphblas-json`` (default
``BENCH_graphblas.json``): the scipy-CSR reference plus the in-repo
dense-grid vs CSR A/B with the compiled peak-HBM estimate (DESIGN.md §2.4).
The algorithms section writes ``--algorithms-json`` (default
``BENCH_algorithms.json``): per-algorithm walls with oracle-parity flags
plus the analyze(algorithms=True) HLO sort count (DESIGN.md §2.5).

The serve section writes ``--serve-json`` (default ``BENCH_serve.json``):
checkpoint/restore/replay walls with the recovered-vs-uninterrupted
bit-identity flag (DESIGN.md §2.7).

The kernel section writes ``--kernels-json`` (default
``BENCH_kernels.json``): the autotune sweep evidence — per-candidate
medians, chosen vs default config, cache-hit flag, roofline fraction of
the chosen config (DESIGN.md §2.9).

``python -m benchmarks.run [--quick] [--n N] [--only PREFIX] [--ab]
[--bench-json PATH] [--graphblas-json PATH] [--algorithms-json PATH]
[--sketches-json PATH] [--serve-json PATH] [--kernels-json PATH]``
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--quick", action="store_true", help="n = 2^17")
    ap.add_argument("--only", default=None)
    ap.add_argument("--ab", action="store_true",
                    help="query section: plan-vs-naive A/B rows")
    ap.add_argument("--bench-json", default="BENCH_queries.json",
                    help="machine-readable query rows (empty string disables)")
    ap.add_argument("--graphblas-json", default="BENCH_graphblas.json",
                    help="machine-readable graphblas A/B rows "
                         "(empty string disables)")
    ap.add_argument("--algorithms-json", default="BENCH_algorithms.json",
                    help="machine-readable graph-algorithm rows "
                         "(empty string disables)")
    ap.add_argument("--sketches-json", default="BENCH_sketches.json",
                    help="machine-readable sketch error-vs-bound rows "
                         "(empty string disables)")
    ap.add_argument("--serve-json", default="BENCH_serve.json",
                    help="machine-readable serve recovery-overhead rows "
                         "(empty string disables)")
    ap.add_argument("--kernels-json", default="BENCH_kernels.json",
                    help="machine-readable kernel autotune-sweep rows "
                         "(empty string disables)")
    args = ap.parse_args()
    n = (1 << 17) if args.quick else args.n

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from . import (bench_algorithms, bench_anonymize, bench_distributed,
                   bench_endtoend, bench_graphblas, bench_io, bench_kernels,
                   bench_queries, bench_serve, bench_sketches)

    sections = [
        ("io", lambda: bench_io.run(n=n)),
        ("query", lambda: bench_queries.run(
            n=n, ab=args.ab, json_path=args.bench_json or None)),
        ("graphblas", lambda: bench_graphblas.run(
            n=n, json_path=args.graphblas_json or None)),
        ("algorithms", lambda: bench_algorithms.run(
            n=n, json_path=args.algorithms_json or None)),
        ("anonymize", lambda: bench_anonymize.run(n=n)),
        ("kernel", lambda: bench_kernels.run(
            quick=args.quick, json_path=args.kernels_json or None)),
        ("distributed", bench_distributed.run),
        ("endtoend", lambda: bench_endtoend.run(n=n)),
        ("sketch", lambda: bench_sketches.run(
            n=n, json_path=args.sketches_json or None)),
        ("serve", lambda: bench_serve.run(
            n=n, json_path=args.serve_json or None)),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in sections:
        if args.only and not name.startswith(args.only):
            continue
        try:
            fn()
        except Exception:
            failed += 1
            print(f"{name}/SECTION_FAILED,0,{traceback.format_exc(limit=1)!r}",
                  flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
