"""CLI for the end-to-end challenge: ``python -m repro.challenge.run``.

Prints the per-phase timing table (paper-style), all 14 Table III query
results (scalars verbatim, vector queries as count + head), the per-window
statistics, cross-window IP overlap and the k heaviest links; ``--verify``
(default) checks every scalar against the sequential NumPy oracle.
``--algorithms`` adds the graph phase (BFS, WCC, PageRank, triangles, or a
comma list of them), each kernel checked against its NumPy oracle.

    PYTHONPATH=src python -m repro.challenge.run --scale 14
    PYTHONPATH=src python -m repro.challenge.run --scale 18 --fused --format pcaplite
    PYTHONPATH=src python -m repro.challenge.run --scale 12 --no-queries \
        --algorithms bfs,wcc,pagerank --pagerank-unweighted
"""
from __future__ import annotations

import argparse
import sys
from typing import Mapping, Optional, Sequence

import numpy as np

from ..core.ref import ref_run_all_queries
from ..core.sketch import SketchConfig, SketchSnapshot
from .pipeline import (
    GRAPH_KERNELS,
    Analytics,
    ChallengeConfig,
    ChallengeRun,
    run_challenge,
)


def format_queries(r) -> str:
    """The 14 Table III queries, in paper order.

    ``r`` is a ChallengeResults — produced by the batch pipeline or by a
    stream snapshot (repro.stream reuses this formatter).
    """
    s = r.scalars

    def group_head(g, agg: str, k: int = 3) -> str:
        n = int(g.n_groups)
        m = min(n, k)
        keys = " ".join(
            "(" + ",".join(str(int(kk[i])) for kk in g.keys) + ")"
            for i in range(m)
        )
        vals = " ".join(str(int(g.aggs[agg][i])) for i in range(m))
        return f"<vector: n={n:,}  head {keys} -> {vals}>"

    rows = [
        ("1  valid packets", int(s.valid_packets)),
        ("2  unique links", int(s.unique_links)),
        ("3  link packet counts", group_head(r.links, "packets")),
        ("4  max link packets", int(s.max_link_packets)),
        ("5  unique sources", int(s.n_unique_sources)),
        ("6  packets per source", group_head(r.per_source, "packets")),
        ("7  max source packets", int(s.max_source_packets)),
        ("8  source fan-out", group_head(r.source_fanout, "count")),
        ("9  max source fan-out", int(s.max_source_fanout)),
        ("10 unique destinations", int(s.n_unique_destinations)),
        ("11 packets per destination", group_head(r.per_destination, "packets")),
        ("12 max destination packets", int(s.max_destination_packets)),
        ("13 destination fan-in", group_head(r.destination_fanin, "count")),
        ("14 max destination fan-in", int(s.max_destination_fanin)),
    ]
    width = max(len(n) for n, _ in rows) + 2
    out = [f"{'query (Table III)':{width}s}result"]
    for name, val in rows:
        out.append(f"{name:{width}s}{val:,}" if isinstance(val, int)
                   else f"{name:{width}s}{val}")
    out.append(f"{'   (unique IPs)':{width}s}{int(s.n_unique_ips):,}")
    return "\n".join(out)


def format_extras(r, nw: int) -> str:
    """Per-window statistics + heaviest links (``r`` as in format_queries)."""
    out = ["", f"per-window statistics ({nw} windows):"]
    keys = ("valid_packets", "unique_links", "n_unique_sources",
            "max_source_fanout")
    out.append(f"{'window':>8s}" + "".join(f"{k:>18s}" for k in keys)
               + f"{'ip_overlap(w-1)':>18s}")
    for wi in range(nw):
        vals = "".join(f"{int(r.windowed[k][wi]):18,}" for k in keys)
        out.append(f"{wi:8d}{vals}{int(r.window_ip_overlap[wi]):18,}")
    act = np.asarray(r.window_activity)
    out.append(
        f"activity histogram: {act.shape[0]} windows x {act.shape[1]} bins "
        f"in one kernel dispatch; busiest bin = {act.max():,.0f} packets"
    )
    k = int(r.top.n_valid)
    out.append(f"\ntop-{k} heaviest links (anonymized ids):")
    out.append(f"{'src':>10s}{'dst':>10s}{'packets':>10s}")
    for i in range(k):
        out.append(f"{int(r.top.src[i]):10d}{int(r.top.dst[i]):10d}"
                   f"{int(r.top.packets[i]):10,}")
    return "\n".join(out)


def format_algorithms(run: ChallengeRun) -> str:
    """Summary of the graph phase (``--algorithms``): each kernel that ran."""
    g = run.graph
    n = int(g.n_live)
    out = ["", f"graph kernels over the anonymized traffic graph "
              f"({n:,} vertices):"]
    k = g.kernels
    if "bfs" in k:
        levels = np.asarray(k["bfs"].levels)[:n]
        reached = levels[levels >= 0]
        out.append(
            f"  bfs        from {int(g.source)}: reached "
            f"{int(k['bfs'].n_reached):,} vertices, max level "
            f"{int(reached.max()) if reached.size else -1}, "
            f"{int(k['bfs'].iterations)} iters, "
            f"converged={bool(k['bfs'].converged)}")
    if "wcc" in k:
        out.append(
            f"  wcc        {int(k['wcc'].n_components):,} weakly connected, "
            f"{int(k['wcc'].iterations)} iters, "
            f"converged={bool(k['wcc'].converged)}")
    if "pagerank" in k:
        pr = k["pagerank"]
        ranks = np.asarray(pr.ranks)[:n]
        top = np.argsort(ranks)[::-1][:3]
        head = " ".join(f"{v}:{ranks[v]:.5f}" for v in top)
        out.append(
            f"  pagerank   residual {float(pr.residual):.2e} after "
            f"{int(pr.iterations)} iters, converged={bool(pr.converged)}, "
            f"top {head}")
    if "triangles" in k:
        out.append(
            f"  triangles  {int(k['triangles'].total):,} closed directed "
            f"wedges (A ⊙ A·A mass)")
    return "\n".join(out)


def verify_algorithms(run: ChallengeRun) -> int:
    """Replay each kernel of the graph phase with the NumPy oracles on the
    anonymized edge list; return the number of disagreeing kernels."""
    from ..kernels.ref import ref_bfs, ref_cc, ref_pagerank, ref_triangles

    g, a = run.graph, run.config.analytics
    n_rows = int(run.anon_table.n_valid)
    src = np.asarray(run.anon_table["src"])[:n_rows].astype(np.int64)
    dst = np.asarray(run.anon_table["dst"])[:n_rows].astype(np.int64)
    n = int(g.n_live)
    k = g.kernels
    bad = []

    if "bfs" in k:
        levels = np.asarray(k["bfs"].levels)
        want = ref_bfs(src, dst, n, int(g.source))
        if not (np.array_equal(levels[:n], want) and np.all(levels[n:] == -1)):
            bad.append("bfs levels")

    if "wcc" in k:
        labels = np.asarray(k["wcc"].labels)
        want = ref_cc(src, dst, n)
        if not (np.array_equal(labels[:n], want) and np.all(labels[n:] == -1)
                and int(k["wcc"].n_components) == len(np.unique(want))):
            bad.append("wcc labels")

    if "pagerank" in k:
        ranks = np.asarray(k["pagerank"].ranks)
        # unweighted: one unit per distinct out-link, not per packet
        e_src, e_dst = ((src, dst) if a.weighted else
                        np.unique(np.stack([src, dst]), axis=1))
        want, _, _ = ref_pagerank(e_src, e_dst, np.ones(len(e_src)), n)
        l1 = np.abs(ranks[:n] - want).sum()
        if not (l1 < 1e-6 and np.all(ranks[n:] == 0.0)):
            bad.append(f"pagerank (L1={l1:.3e})")

    if "triangles" in k:
        per_node = np.asarray(k["triangles"].per_node)
        want, total = ref_triangles(src, dst, n)
        if not (np.array_equal(per_node[:n], want.astype(np.float32))
                and int(k["triangles"].total) == total):
            bad.append("triangle counts")
    for what in bad:
        print(f"MISMATCH {what} vs oracle", file=sys.stderr)
    return len(bad)


# --- the approximate (sketch) tier --------------------------------------------

def run_sketch_tier(
    capture: Mapping[str, np.ndarray],
    cfg: SketchConfig,
    *,
    batch_capacity: int = 1 << 15,
    backend: str = "auto",
    top_k: int = 10,
) -> SketchSnapshot:
    """Fold the whole capture through the bounded-memory sketch tier
    (:mod:`repro.core.sketch`) in fixed-capacity micro-batches — the batch
    pipeline's counterpart of ``StreamConfig(tier="sketch")``."""
    import jax
    import jax.numpy as jnp

    from ..core.sketch import init_sketch, snapshot_sketch
    from ..stream.engine import _jitted_sketch_update

    src = np.asarray(capture["src"], np.int64)
    dst = np.asarray(capture["dst"], np.int64)
    state = init_sketch(cfg)
    # the stream engine's cached fold: repeated runs reuse one executable
    update = _jitted_sketch_update(backend, jax.default_backend() != "cpu")
    for off in range(0, len(src), batch_capacity):
        s = src[off:off + batch_capacity]
        d = dst[off:off + batch_capacity]
        n = len(s)
        pad = batch_capacity - n
        state = update(
            state,
            jnp.asarray(np.pad(s, (0, pad)), jnp.int32),
            jnp.asarray(np.pad(d, (0, pad)), jnp.int32),
            n,
        )
    jax.block_until_ready(state)
    return snapshot_sketch(state, k=top_k)


def format_sketch(snap: SketchSnapshot) -> str:
    """Sketch-tier report: estimates with their configured error bounds."""
    b = snap.bounds
    out = [
        "",
        f"sketch tier (bounded memory, overflow={snap.overflow} by "
        "construction):",
        f"  valid packets            {snap.n_packets:,} (exact counter)",
        f"  unique sources           ~{snap.unique_sources:,.0f}  "
        f"(HLL, rel tol {b['hll_rel_tolerance']:.3f})",
        f"  unique destinations      ~{snap.unique_destinations:,.0f}",
        f"  unique links             ~{snap.unique_links:,.0f}",
        f"  max link packets         ~{snap.max_link_packets:,.0f}  "
        f"(+{b['cms_epsilon_n']:,.1f} / -{b['heavy_link_offset']:,.0f})",
        f"  max source packets       ~{snap.max_source_packets:,.0f}  "
        f"(+{b['cms_epsilon_n']:,.1f} / -{b['heavy_src_offset']:,.0f})",
    ]
    k = min(snap.n_top_talkers, 5)
    if k:
        head = "  ".join(
            f"{int(snap.top_talker_src[i])}:{int(snap.top_talker_packets[i])}"
            for i in range(k)
        )
        out.append(f"  top talkers (est <= true + offset)   {head}")
    k = min(snap.n_top_links, 5)
    if k:
        head = "  ".join(
            f"({int(snap.top_link_src[i])},{int(snap.top_link_dst[i])}):"
            f"{int(snap.top_link_packets[i])}"
            for i in range(k)
        )
        out.append(f"  top links                            {head}")
    return "\n".join(out)


def verify_sketch(snap: SketchSnapshot, exact: Mapping[str, int]) -> int:
    """Check every sketch estimate against its configured theoretical bound
    given the exact answers; return the number of violations.

    ``exact`` maps the scalar names (``valid_packets``, ``unique_links``,
    ``n_unique_sources``, ``n_unique_destinations``, ``max_link_packets``,
    ``max_source_packets``) to the exact-tier values.  Bounds checked:
    HLL relative error within tolerance; maxima within
    ``[-heavy offset, +CMS εN]``; the packet counter bit-exact.
    """
    b = snap.bounds
    bad = 0

    def fail(msg: str) -> None:
        nonlocal bad
        print(f"SKETCH BOUND VIOLATION: {msg}", file=sys.stderr)
        bad += 1

    if snap.n_packets != int(exact["valid_packets"]):
        fail(f"valid_packets {snap.n_packets} != {exact['valid_packets']}")
    tol = b["hll_rel_tolerance"]
    for name, est in [
        ("n_unique_sources", snap.unique_sources),
        ("n_unique_destinations", snap.unique_destinations),
        ("unique_links", snap.unique_links),
    ]:
        want = int(exact[name])
        rel = abs(est - want) / max(want, 1)
        if rel > tol:
            fail(f"{name} est {est:.0f} vs exact {want}: rel {rel:.4f} > "
                 f"tol {tol:.4f}")
    for name, est, off_key in [
        ("max_link_packets", snap.max_link_packets, "heavy_link_offset"),
        ("max_source_packets", snap.max_source_packets, "heavy_src_offset"),
    ]:
        want = int(exact[name])
        lo = want - b[off_key]
        hi = want + b["cms_epsilon_n"]
        if not lo <= est <= hi:
            fail(f"{name} est {est:.0f} outside [{lo:.1f}, {hi:.1f}] "
                 f"(exact {want})")
    return bad


def verify_scalars(run: ChallengeRun) -> int:
    """Compare every scalar to the NumPy oracle; return mismatch count."""
    cap = run.capture
    ref = ref_run_all_queries(cap["src"].astype(np.int64),
                              cap["dst"].astype(np.int64))
    bad = 0
    for k, v in ref.items():
        got = int(getattr(run.results.scalars, k))
        if got != v:
            print(f"MISMATCH {k}: pipeline={got} oracle={v}", file=sys.stderr)
            bad += 1
    return bad


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.challenge.run",
        description="End-to-end Anonymized Network Sensing Graph Challenge",
    )
    ap.add_argument("--scale", type=int, default=14,
                    help="2^scale packets over 2^scale RMAT vertices")
    ap.add_argument("--n-packets", type=int, default=None,
                    help="override packet count (default 2^scale)")
    ap.add_argument("--windows", type=int, default=8)
    ap.add_argument("--ip-bins", type=int, default=1024)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--method", default="shuffle", choices=["shuffle", "hash"])
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--format", default="plq", choices=["plq", "pcaplite"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "xla", "pallas", "interpret"])
    ap.add_argument("--fused", action="store_true",
                    help="also time build+anonymize+analyze as one program")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep Pallas block configs at this run's kernel "
                         "shapes first and persist the winners "
                         "(configs/autotune/<backend>.json); without it, "
                         "cached tables are used when present, defaults "
                         "otherwise")
    ap.add_argument("--distributed", action="store_true",
                    help="scalar suite via shard_map over local devices")
    ap.add_argument("--algorithms", nargs="?", const=",".join(GRAPH_KERNELS),
                    default="", metavar="KERNELS",
                    help="graph phase over the anonymized traffic graph: a "
                         "comma list from " + ",".join(GRAPH_KERNELS) +
                         " (all four when given bare; oracle-checked under "
                         "--verify)")
    ap.add_argument("--bfs-source", type=int, default=None,
                    help="BFS source vertex (anonymized id; default the "
                         "source of the first packet)")
    ap.add_argument("--pagerank-unweighted", action="store_true",
                    help="split rank evenly over distinct out-links "
                         "(Graphalytics) instead of by packet count")
    ap.add_argument("--no-queries", dest="queries", action="store_false",
                    help="skip the Table III queries (graph phase only)")
    ap.add_argument("--workdir", default=None,
                    help="capture cache dir (tmp if unset)")
    ap.add_argument("--tier", default="exact",
                    choices=["exact", "sketch", "both"],
                    help="also run the bounded-memory sketch tier beside "
                         "the exact pipeline (sketch/both; under --verify "
                         "every estimate is gated against its error bound)")
    ap.add_argument("--sketch-depth", type=int, default=4,
                    help="Count-Min depth (rows)")
    ap.add_argument("--sketch-width", type=int, default=4096,
                    help="Count-Min width (cells per row)")
    ap.add_argument("--hll-p", type=int, default=12,
                    help="HyperLogLog precision: 2^p registers")
    ap.add_argument("--heavy-capacity", type=int, default=64,
                    help="space-saving heavy-hitter counters")
    ap.add_argument("--no-verify", dest="verify", action="store_false",
                    help="skip the NumPy-oracle scalar check")
    args = ap.parse_args(argv)
    if args.tier != "exact" and not args.queries:
        ap.error("--tier sketch/both is checked against the queries; drop "
                 "--no-queries")
    if argv is None:  # the command line, not a caller passing its own argv
        from ..launch.compile_cache import use_compile_cache

        use_compile_cache()

    try:
        analytics = Analytics(
            queries=args.queries,
            kernels=tuple(k for k in args.algorithms.split(",") if k),
            bfs_source=args.bfs_source, weighted=not args.pagerank_unweighted)
        cfg = ChallengeConfig(
            scale=args.scale, n_packets=args.n_packets, n_windows=args.windows,
            ip_bins=args.ip_bins, top_k=args.top_k, method=args.method,
            rounds=args.rounds, seed=args.seed, fmt=args.format,
            backend=args.backend, fused=args.fused,
            distributed=args.distributed, analytics=analytics,
            workdir=args.workdir,
        )
    except ValueError as e:
        ap.error(str(e))
    if args.autotune:
        # sweep at THIS run's kernel shapes so the persisted table has hot
        # entries for every dispatch the pipeline is about to make; later
        # runs (and the jitted pipeline below) read the table through
        # best_config without re-sweeping
        from repro.kernels import autotune as _autotune

        cap = cfg.table_capacity
        for kernel, kn, num_out in (
            ("histogram", cap, cfg.n_windows * cfg.ip_bins),
            ("segreduce", cap, cap + 1),
        ):
            entry = _autotune.sweep_and_save(kernel, kn, num_out, "float32")
            print(f"autotune {kernel}: n={kn} out={num_out} -> "
                  f"{entry['config']} ({entry['us']:.0f}us vs default "
                  f"{entry['default_us']:.0f}us)")
    print(f"anonymized network sensing challenge: {cfg.packets:,} packets, "
          f"{cfg.n_windows} windows, fmt={cfg.fmt}, method={cfg.method}")
    run = run_challenge(cfg)

    print("\n" + run.timings.format_table())
    if run.results is not None:
        print()
        print(format_queries(run.results))
        print(format_extras(run.results, run.config.n_windows))
    if run.graph is not None:
        print(format_algorithms(run))

    sketch_snap = None
    if args.tier != "exact":
        # the batch pipeline always computes the exact tier (it IS the
        # challenge); sketch/both adds the approximate tier beside it and,
        # under --verify, gates every estimate against its bound
        try:
            sketch_cfg = SketchConfig(
                cms_depth=args.sketch_depth, cms_width=args.sketch_width,
                hll_p=args.hll_p, heavy_capacity=args.heavy_capacity,
                seed=args.seed,
            )
        except ValueError as e:
            ap.error(str(e))
        sketch_snap = run_sketch_tier(
            run.capture, sketch_cfg, backend=args.backend, top_k=args.top_k
        )
        print(format_sketch(sketch_snap))

    if args.verify:
        bad = verify_scalars(run) if run.results is not None else 0
        if run.graph is not None:
            bad += verify_algorithms(run)
        if sketch_snap is not None:
            s = run.results.scalars
            bad += verify_sketch(sketch_snap, {
                "valid_packets": int(s.valid_packets),
                "unique_links": int(s.unique_links),
                "n_unique_sources": int(s.n_unique_sources),
                "n_unique_destinations": int(s.n_unique_destinations),
                "max_link_packets": int(s.max_link_packets),
                "max_source_packets": int(s.max_source_packets),
            })
        if bad:
            print(f"\n{bad} result(s) disagree with the oracle", file=sys.stderr)
            return 1
        if run.results is not None:
            print("\nall scalar queries match the NumPy oracle ✓")
        if run.graph is not None:
            print(f"graph kernels {','.join(run.graph.kernels)} match their "
                  "NumPy oracles ✓")
        if sketch_snap is not None:
            print("all sketch estimates within their configured bounds ✓")
    return 0


if __name__ == "__main__":
    sys.exit(main())
