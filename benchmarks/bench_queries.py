"""Paper Fig. 1 + Table III: per-query speedup of jaxdf (jit, XLA) over the
sequential NumPy oracle (the single-core "Pandas" role).

Reports each of the challenge queries individually (as the paper's Fig. 1
does) and the all-14-queries pipeline, asserting query-for-query equality
against the ``core/ref.py`` oracle.

Every row is also recorded machine-readably (steady-state us/call + the
number of sort ops in the query's compiled HLO) and written to
``BENCH_queries.json`` when a path is given — the trajectory file
``benchmarks/run.py`` emits.

    PYTHONPATH=src python -m benchmarks.bench_queries [--n N] [--json P]
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Table, run_all_queries
from repro.core import queries as Q
from repro.core.plan import count_hlo_sorts
from repro.core.ref import ref_run_all_queries, ref_traffic_matrix
from repro.core.temporal import windowed_queries

from .common import (device_kind, emit, kernel_roofline, packet_arrays,
                     run_manifest, time_fn)

QUERIES = {
    "valid_packets": (Q.valid_packets, lambda s, d: int(len(s))),
    "unique_links": (Q.unique_links,
                     lambda s, d: len(ref_traffic_matrix(s, d)[0])),
    "max_link_packets": (Q.max_link_packets,
                         lambda s, d: int(ref_traffic_matrix(s, d)[2].max())),
    "unique_sources": (lambda t: Q.unique_sources(t).n_unique,
                       lambda s, d: len(np.unique(s))),
    "unique_ips": (lambda t: Q.unique_ips(t).n_unique,
                   lambda s, d: len(np.unique(np.concatenate([s, d])))),
    "max_source_packets": (Q.max_source_packets,
                           lambda s, d: int(np.unique(s, return_counts=True)[1].max())),
    "max_source_fanout": (Q.max_source_fanout,
                          lambda s, d: int(np.unique(
                              ref_traffic_matrix(s, d)[0], return_counts=True)[1].max())),
    "max_dest_fanin": (Q.max_destination_fanin,
                       lambda s, d: int(np.unique(
                           ref_traffic_matrix(s, d)[1], return_counts=True)[1].max())),
}


def _hlo_sorts(jitted, *args) -> int:
    """Sort ops in the compiled (post-CSE) HLO of ``jitted(*args)``."""
    return count_hlo_sorts(jitted.lower(*args).compile().as_text())


def _assert_oracle(res, ref: Dict[str, int], label: str) -> None:
    bad = {k: (int(getattr(res, k)), v)
           for k, v in ref.items() if int(getattr(res, k)) != v}
    if bad:
        raise AssertionError(f"{label} diverges from the NumPy oracle: {bad}")


def run(
    n: int = 1 << 20,
    iters: int = 3,
    json_path: Optional[str] = None,
) -> Dict[str, Dict[str, float]]:
    rows: Dict[str, Dict[str, float]] = {}

    def record(name, seconds, derived="", sorts=None):
        emit(f"query/{name}", seconds, derived)
        entry: Dict[str, float] = {"us_per_call": seconds * 1e6}
        if sorts is not None:
            entry["hlo_sorts"] = sorts
        rows[name] = entry

    src, dst = packet_arrays(n)
    t = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst)})

    for name, (jq, refq) in QUERIES.items():
        jf = jax.jit(jq)
        t_jax = time_fn(jf, t, iters=iters)
        t_ref = time_fn(lambda: refq(src, dst), iters=max(iters - 1, 1))
        got = int(jf(t)) if np.ndim(jf(t)) == 0 else None
        want = refq(src, dst)
        ok = (got == want) if got is not None else True
        record(name, t_jax,
               f"speedup_vs_numpy={t_ref / t_jax:.1f}x correct={ok}",
               sorts=_hlo_sorts(jf, t))

    jall = jax.jit(run_all_queries)
    t_all = time_fn(jall, t, iters=iters)
    t_ref_all = time_fn(lambda: ref_run_all_queries(src, dst), iters=1)
    ref = ref_run_all_queries(src, dst)
    _assert_oracle(jall(t), ref, "all14_plan")
    record("all14_pipeline", t_all,
           f"speedup_vs_numpy={t_ref_all / t_all:.1f}x correct=True n={n}",
           sorts=_hlo_sorts(jall, t))

    # multi-temporal (Kepner et al. [14]): all stats × 16 windows, one pass
    ts = jnp.asarray(np.sort(np.random.default_rng(0).integers(0, 1 << 20, n))
                     .astype(np.int32))
    tw = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst),
                          "ts": ts})
    jwin = jax.jit(lambda t: windowed_queries(t, (1 << 20) // 16, 16))
    t_win = time_fn(jwin, tw, iters=iters)
    record("windowed16_pipeline", t_win,
           f"16 windows fused, "
           f"{t_win / t_all:.2f}x of single-window cost n={n}",
           sorts=_hlo_sorts(jwin, tw))

    # ---- roofline: the challenge kernels + the all-14 program, achieved
    # bytes/s and flops/s vs the backend peak (ROADMAP item 5; the fractions
    # are what the CI gate pins as non-null) ----
    roofline = _roofline_section(t, jall, t_all, src, iters)
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


def _roofline_section(t, jall, t_all: float, src: np.ndarray,
                      iters: int) -> Dict[str, Dict]:
    """Achieved-vs-peak for the three challenge kernels + the full suite.

    The kernels run at their bench shapes (ids from the same RMAT packet
    stream, 1024 bins/segments, a 4x2048 CMS) on the dispatch path the
    engine uses (``backend="auto"``); the all-14 row reuses the already
    compiled+timed program rather than re-measuring it.
    """
    from repro.kernels.ops import cms_update, histogram, segmented_reduce
    from repro.launch.roofline import program_roofline

    n = src.shape[0]
    bins = 1024
    ids = jnp.asarray(src.astype(np.int32) % bins)
    vals = jnp.ones((n,), jnp.float32)
    depth, width = 4, 2048
    counts = jnp.zeros((depth, width), jnp.int32)
    cols = jnp.asarray(
        np.random.default_rng(1).integers(0, width, (depth, n)).astype(np.int32)
    )
    props = jnp.ones((n,), jnp.int32)

    out = {
        "histogram": kernel_roofline(
            lambda i: histogram(i, bins), ids, iters=iters),
        "segmented_reduce": kernel_roofline(
            lambda v, s: segmented_reduce(v, s, bins, op="max"),
            vals, ids, iters=iters),
        "cms_update": kernel_roofline(
            lambda c, ci, p: cms_update(c, ci, p),
            counts, cols, props, iters=iters),
        "all14_pipeline": program_roofline(
            jall.lower(t).compile().as_text(), t_all, device_kind()),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--quick", action="store_true", help="n = 2^14")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable rows (BENCH_queries.json)")
    args = ap.parse_args(argv)
    n = (1 << 14) if args.quick else args.n
    print("name,us_per_call,derived")
    run(n=n, iters=args.iters, json_path=args.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
