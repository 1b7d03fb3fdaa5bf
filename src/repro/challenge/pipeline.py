"""End-to-end Anonymized Network Sensing pipeline (DESIGN.md §7).

The paper's defining feature is that the challenge is measured as one
*workload*, not a kernel: data I/O, graph-table construction, anonymization
and the 14 Table III queries timed as phases of a single run.  This module
is that orchestrator:

  read       host I/O — generate-or-reuse a synthetic RMAT capture, store it
             columnar (plq) or row-major (pcaplite), read it back
             (paper Table II's PCAP -> Parquet -> cached protocol);
  build      packet-Table construction: temporal window ids, device
             transfer, and the (src, dst) group-by that materializes the
             traffic matrix A_t (paper: ``df.groupby(['src','dst'])``);
  anonymize  unique -> shuffle -> gather over the IP domain (paper §IV);
  analyze    every Table III query (scalar + vector forms), the
             multi-temporal windowed suite, cross-window IP overlap
             (semi-join), top-k heaviest links, and a per-window source
             activity histogram batched through the Pallas histogram kernel
             in one dispatch (kernels/ops.windowed_histogram);
  graph      the graph kernels (BFS, WCC, PageRank, triangles) over the
             anonymized traffic graph: one plan program builds the CSR
             pair, then each selected kernel runs as its own program.

``ChallengeConfig.analytics`` picks what follows anonymize: the Table III
suite (``analyze``, the default), graph kernels, or both.

Each phase is timed with ``block_until_ready`` walls (`ChallengePhaseTimings`
mirrors the paper's per-phase tables); ``fused=True`` additionally compiles
build->anonymize->analyze into ONE jitted, buffer-donated program — the
"whole workload is one XLA computation" measurement no per-phase timing can
see.  ``distributed=True`` runs the scalar suite via shard_map
(dist/relational.py) over all local devices.  Each phase program is built
once per process, keyed by the config's statics, so a repeat run of a config
traces and compiles nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import tempfile
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import algorithms as algo
from ..core.anonymize import anonymize
from ..obs import counter_event, span as obs_span
from ..core.ops import factorize, groupby_aggregate, mix32, semi_join, unique
from ..core.plan import lead_fanout, lead_groups, link_groups, unique_lead
from ..core.queries import (
    QueryResults,
    TopLinks,
    packet_weights,
    run_all_queries_naive,
    scalar_queries_from_plans,
    table_csrs,
    table_plans,
    top_links,
    top_links_from_plan,
    traffic_matrix,
    unique_ips,
)
from ..core.table import Table
from ..core.temporal import windowed_queries, windowed_queries_naive
from ..data import pcaplite
from ..data.plq import read_plq, write_plq
from ..data.rmat import synthetic_packets
from ..kernels.ops import histogram, windowed_histogram

__all__ = [
    "Analytics",
    "ChallengeConfig",
    "ChallengePhaseTimings",
    "ChallengeResults",
    "ChallengeRun",
    "GRAPH_KERNELS",
    "GraphRun",
    "TrafficGraph",
    "cross_window_ip_overlap",
    "cross_window_ip_overlap_naive",
    "analyze",
    "analyze_peak_buffer_bytes",
    "distributed_scalar_queries",
    "graph_kernel",
    "graph_plan",
    "graph_vertices",
    "run_challenge",
    "timings_from_spans",
]

PHASES = ("read", "build", "anonymize", "analyze", "graph")

#: the graph kernels a pass can run, in the order it runs them
GRAPH_KERNELS = ("bfs", "wcc", "pagerank", "triangles")

#: BFS levels and WCC labels ride float32 carriers (core/algorithms.py),
#: exact for vertex ids below 2**24
MAX_GRAPH_VERTICES = 1 << 24


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Analytics:
    """What a pass computes from the anonymized table.

    ``queries`` runs the Table III suite (the ``analyze`` phase);
    ``kernels`` names the graph kernels of the ``graph`` phase, from
    :data:`GRAPH_KERNELS`.  ``bfs_source`` is an anonymized vertex id, or
    None for the anonymized source of the capture's first packet, a vertex
    with at least one out-link that changes with the capture.  Either way
    it reaches the BFS program as an operand, so a new source compiles
    nothing.  PageRank splits rank over out-links by packet count
    (``weighted``) or evenly over distinct out-links (Graphalytics); it
    runs :func:`repro.core.algorithms.pagerank`'s damping 0.85 and stopping
    rule (an L1 change below 1e-6, at most 100 steps).
    """

    queries: bool = True
    kernels: Tuple[str, ...] = ()
    bfs_source: Optional[int] = None
    weighted: bool = True

    def __post_init__(self):
        unknown = [k for k in self.kernels if k not in GRAPH_KERNELS]
        if unknown or len(set(self.kernels)) != len(self.kernels):
            raise ValueError(f"graph kernels must be distinct names from "
                             f"{GRAPH_KERNELS}, got {self.kernels}")
        if not self.queries and not self.kernels:
            raise ValueError("analytics selects nothing: enable the queries "
                             "or name at least one graph kernel")


def graph_vertices(capacity: int) -> int:
    """The graph phase's static vertex domain for a table of ``capacity``
    rows: anonymized ids are below the distinct-IP count, which both
    endpoints of every row bound by ``2 * capacity``.  Refuses a domain
    past :data:`MAX_GRAPH_VERTICES`, where the float32 level and label
    carriers stop being exact."""
    n = 2 * capacity
    if n > MAX_GRAPH_VERTICES:
        raise ValueError(
            f"the graph kernels' vertex domain 2 * capacity = {n} exceeds "
            f"2**24: BFS levels and WCC labels ride float32 and would no "
            f"longer be exact; use a table capacity of at most 2**23")
    return n


@dataclasses.dataclass(frozen=True)
class ChallengeConfig:
    """One end-to-end challenge run.

    ``scale`` plays the Graph500 role: 2**scale packets over 2**scale RMAT
    vertices (the challenge's hypersparse regime).  ``n_packets`` overrides
    the packet count independently of the vertex scale.
    """

    scale: int = 14
    n_packets: Optional[int] = None
    capacity: Optional[int] = None       # static table rows (>= n_packets)
    n_windows: int = 8                   # temporal windows (static)
    ip_bins: int = 1024                  # hashed per-window activity bins
    top_k: int = 10                      # heaviest links to report
    method: str = "shuffle"              # 'shuffle' | 'hash' (core/anonymize)
    rounds: int = 1
    warm: bool = True                    # compile phases before timing them
    seed: int = 0
    fmt: str = "plq"                     # 'plq' | 'pcaplite'
    backend: str = "auto"                # histogram kernel dispatch
    fused: bool = False                  # also time the one-program path
    fused_epilogue: bool = False         # fused kernel epilogues in analyze
    distributed: bool = False            # scalar suite via shard_map
    analytics: Analytics = Analytics()   # Table III suite and/or graph kernels
    workdir: Optional[str] = None        # capture cache dir (tmp if None)

    def __post_init__(self):
        if self.packets < 1:
            raise ValueError("need at least 1 packet (the static-shape engine "
                             "has no zero-capacity buffers)")
        for field in ("n_windows", "ip_bins", "top_k"):
            if getattr(self, field) < 1:
                raise ValueError(f"{field} must be >= 1")
        if (self.fused or self.distributed) and not self.analytics.queries:
            raise ValueError("fused and distributed run the Table III "
                             "queries; enable analytics.queries")

    @property
    def packets(self) -> int:
        return self.n_packets if self.n_packets is not None else 1 << self.scale

    @property
    def table_capacity(self) -> int:
        cap = self.capacity if self.capacity is not None else self.packets
        if cap < self.packets:
            raise ValueError(f"capacity {cap} < n_packets {self.packets}")
        return cap

    def capture_path(self, workdir: str) -> str:
        name = f"capture_s{self.scale}_n{self.packets}_seed{self.seed}.{self.fmt}"
        return os.path.join(workdir, name)


# ---------------------------------------------------------------------------
# timings record
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ChallengePhaseTimings:
    """Per-phase wall seconds + derived throughput (paper-table shape)."""

    n_packets: int
    read_s: float
    build_s: float
    anonymize_s: float
    analyze_s: Optional[float]           # None when the queries are off
    graph_s: Optional[float] = None      # None when no graph kernel runs
    fused_s: Optional[float] = None      # one-program build+anonymize+analyze
    compile_s: Optional[float] = None    # warm pass (trace+compile+first run)
                                         # excluded from the phase walls when
                                         # ChallengeConfig.warm is set; a
                                         # config already run in the process
                                         # reads a cache hit here

    def phases(self) -> Dict[str, float]:
        """Wall seconds of each phase that ran, in run order."""
        walls = {p: getattr(self, f"{p}_s") for p in PHASES}
        return {p: s for p, s in walls.items() if s is not None}

    @property
    def total_s(self) -> float:
        return sum(self.phases().values())

    def packets_per_s(self, phase: str = "total") -> float:
        s = self.total_s if phase == "total" else getattr(self, f"{phase}_s")
        return self.n_packets / s if s and s > 0 else float("inf")

    def as_dict(self) -> Dict[str, float]:
        d = {f"{p}_s": s for p, s in self.phases().items()}
        d["total_s"] = self.total_s
        if self.fused_s is not None:
            d["fused_s"] = self.fused_s
        if self.compile_s is not None:
            d["compile_s"] = self.compile_s
        return d

    def format_table(self) -> str:
        rows = [f"{'phase':12s}{'seconds':>12s}{'packets/sec':>16s}"]
        for p, s in self.phases().items():
            rows.append(f"{p:12s}{s:12.4f}{self.n_packets / max(s, 1e-12):16,.0f}")
        rows.append(
            f"{'total':12s}{self.total_s:12.4f}"
            f"{self.n_packets / max(self.total_s, 1e-12):16,.0f}"
        )
        if self.fused_s is not None:
            rows.append(
                f"{'fused(b+a+a)':12s}{self.fused_s:12.4f}"
                f"{self.n_packets / max(self.fused_s, 1e-12):16,.0f}"
            )
        if self.compile_s is not None:
            rows.append(f"{'(compile)':12s}{self.compile_s:12.4f}"
                        f"{'excluded above':>16s}")
        return "\n".join(rows)


def timings_from_spans(records) -> ChallengePhaseTimings:
    """Rebuild :class:`ChallengePhaseTimings` from exported span records.

    The inverse of the span wiring in :func:`run_challenge`: given the
    record dicts of one telemetry export (``repro.obs.read_jsonl`` output,
    or ``get_tracer().records()`` directly), find the LAST completed
    ``challenge`` span group and reassemble the phase walls (``analyze``
    and ``graph`` each None where the run had no such phase).  Because both
    the live dataclass and this replay read the very same span durations —
    and JSON serializes floats via shortest-round-trip repr — the result is
    bit-identical to the ``ChallengeRun.timings`` of that run (asserted in
    tests/test_obs.py and the CI telemetry smoke).
    """
    group: Dict[str, dict] = {}
    last: Optional[Dict[str, dict]] = None
    for rec in records:
        if rec.get("kind") != "span":
            continue
        if rec.get("parent") == "challenge":
            group[rec["name"]] = rec
        elif rec.get("name") == "challenge" and rec.get("parent") is None:
            last = {**group, "challenge": rec}
            group = {}
    if last is None:
        raise ValueError("no completed 'challenge' span group in records")
    missing = [p for p in ("read", "build_host", "build_device",
                           "anonymize") if p not in last]
    if missing or ("analyze" not in last and "graph" not in last):
        raise ValueError(f"challenge span group incomplete: missing "
                         f"{missing or ['analyze or graph']}")
    dur = lambda name: last[name]["duration_s"] if name in last else None
    return ChallengePhaseTimings(
        n_packets=int(last["challenge"]["attrs"]["n_packets"]),
        read_s=dur("read"),
        build_s=dur("build_host") + dur("build_device"),
        anonymize_s=dur("anonymize"),
        analyze_s=dur("analyze"),
        graph_s=dur("graph"),
        fused_s=dur("fused"),
        compile_s=dur("compile"),
    )


# ---------------------------------------------------------------------------
# analysis results (one jit-able pytree)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChallengeResults:
    """Everything the analyze phase produces, tail-padded static buffers.

    The 14 Table III queries: the ten scalars in ``scalars`` plus the vector
    forms ``links`` (Q3), ``unique_sources``/``unique_destinations`` (Q5/Q10
    values), ``per_source``/``per_destination`` (Q6/Q11) and
    ``source_fanout``/``destination_fanin`` (Q8/Q13).  Beyond Table III:
    per-window statistics, the batched per-window activity histogram, the
    cross-window IP overlap and the k heaviest links.
    """

    scalars: QueryResults
    links: "jax.Array | object"
    per_source: object
    per_destination: object
    source_fanout: object
    destination_fanin: object
    unique_sources: object
    unique_destinations: object
    top: TopLinks
    windowed: Dict[str, jnp.ndarray]
    window_activity: jnp.ndarray      # (n_windows, ip_bins) float32
    window_ip_overlap: jnp.ndarray    # (n_windows,) int32


jax.tree_util.register_dataclass(
    ChallengeResults,
    data_fields=[f.name for f in dataclasses.fields(ChallengeResults)],
    meta_fields=[],
)


@dataclasses.dataclass
class GraphRun:
    """What the graph phase produced: each selected kernel's result by
    name (``bfs``: :class:`~repro.core.algorithms.BfsResult`, ``wcc``:
    ``ComponentsResult``, ``pagerank``: ``PageRankResult``, ``triangles``:
    ``TriangleResult``), the live vertex count (anonymized ids are
    ``[0, n_live)``) and the BFS source used, as scalars."""

    kernels: Dict[str, object]
    n_live: jax.Array
    source: jax.Array


@dataclasses.dataclass
class ChallengeRun:
    """A finished run: device results + timings + the host capture columns.

    ``results`` is None when the queries are off, ``graph`` None when no
    graph kernel ran.  ``anon_table`` is the anonymized device table both
    phases ran on, so a caller can re-run analyze on exactly that input
    (chip_smoke.py compares kernel backends on it) and whose live prefix
    is the edge list the NumPy oracles replay (challenge/run.py --verify).
    """

    results: Optional[ChallengeResults]
    timings: ChallengePhaseTimings
    capture: Dict[str, np.ndarray]
    config: ChallengeConfig
    anon_table: Optional[Table] = None
    graph: Optional[GraphRun] = None


# ---------------------------------------------------------------------------
# phase: read
# ---------------------------------------------------------------------------

def read_phase(cfg: ChallengeConfig, workdir: str) -> Dict[str, np.ndarray]:
    """Generate-or-reuse the capture file; return host columns.

    Re-reading an existing file is the paper's "cached" fast path — the
    generator only runs on the first call for a given (scale, n, seed, fmt).
    """
    path = cfg.capture_path(workdir)
    if not os.path.exists(path):
        cols = synthetic_packets(cfg.packets, scale=cfg.scale, seed=cfg.seed)
        if cfg.fmt == "plq":
            write_plq(path, cols)
        elif cfg.fmt == "pcaplite":
            pcaplite.write_pcaplite(path, cols)
        else:
            raise ValueError(f"unknown capture format {cfg.fmt!r}")
    if cfg.fmt == "plq":
        return read_plq(path, ["ts", "src", "dst"])
    return {k: v for k, v in pcaplite.parse_fast(path).items()
            if k in ("ts", "src", "dst")}


# ---------------------------------------------------------------------------
# phase: build
# ---------------------------------------------------------------------------

def window_column(ts: np.ndarray, n_windows: int) -> np.ndarray:
    """Host-side temporal window ids covering the capture's full ts range.

    Computed in int64 on the host (capture timestamps are u64 cumsums that
    overflow int32; the *window id* always fits — n_windows is small).
    """
    ts = np.asarray(ts).astype(np.int64)
    t0 = ts.min() if len(ts) else 0
    span = (ts.max() - t0 + 1) if len(ts) else 1
    wlen = -(-int(span) // n_windows)  # ceil
    return np.minimum((ts - t0) // wlen, n_windows - 1).astype(np.int32)


def build_columns(
    cols: Dict[str, np.ndarray], cfg: ChallengeConfig
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """(src, dst, win) padded to static capacity, + live-row count."""
    n = len(cols["src"])
    cap = max(cfg.table_capacity, n)
    pad = lambda a, fill: np.concatenate(
        [a.astype(np.int32), np.full(cap - n, fill, np.int32)]
    )
    win = window_column(cols["ts"], cfg.n_windows)
    # win padding is 0 (not -1): windowed_queries clips; analyze masks rows.
    return pad(cols["src"], 0), pad(cols["dst"], 0), pad(win, 0), n


def build_table(src, dst, win, n_valid) -> Table:
    return Table(
        columns={"src": jnp.asarray(src), "dst": jnp.asarray(dst),
                 "win": jnp.asarray(win)},
        n_valid=jnp.asarray(n_valid, jnp.int32),
    )


# ---------------------------------------------------------------------------
# phase: analyze
# ---------------------------------------------------------------------------

def cross_window_ip_overlap(
    t: Table, n_windows: int, backend: str = "auto",
    ips: Optional[object] = None, method: str = "scan",
) -> jnp.ndarray:
    """overlap[w] = |distinct IPs active in window w AND window w-1|.

    Sort-once form (DESIGN.md §2.3): every endpoint's rank in the sorted
    distinct-IP domain (``unique_ips`` — the plan's one concat sort, shared
    with the scalar suite when the caller passes ``ips``) is a binary
    search, so per-window IP activity is a boolean presence vector over IP
    ranks and adjacent-window AND + popcount answers the persistence
    question with ZERO sorts beyond the shared one.  The pre-plan
    formulation re-sorted what the group-by had just sorted (see
    :func:`cross_window_ip_overlap_naive`).  overlap[0] == 0 by
    construction.

    ``method="scan"`` (default, DESIGN.md §2.4) walks the window axis with
    a ``lax.scan`` carrying ONE window's presence vector — O(ip_capacity)
    peak memory; ``method="grid"`` scatters the full
    ``(n_windows + 1, ip_capacity + 1)`` presence grid at once — the dense
    A/B baseline, O(n_windows × ip_capacity) peak, bit-identical results.
    ``backend`` is accepted for signature compatibility; no histogram
    dispatch remains on this path.
    """
    del backend
    if ips is None:
        ips = unique_ips(t)
    valid = t.valid_mask()
    nw = n_windows
    ip_cap = ips.values.shape[0]
    # out-of-range window ids are DROPPED (dump row), matching the naive
    # path's histogram semantics — not clamped into the edge windows
    in_range = valid & (t["win"] >= 0) & (t["win"] < nw)
    win = jnp.where(in_range, t["win"], nw)
    r_src = jnp.minimum(factorize(t["src"], ips.values), ip_cap)
    r_dst = jnp.minimum(factorize(t["dst"], ips.values), ip_cap)
    if method == "grid":
        grid = jnp.zeros((nw + 1, ip_cap + 1), jnp.bool_)
        grid = grid.at[win, r_src].set(True)
        grid = grid.at[win, r_dst].set(True)
        live = grid[:nw, :ip_cap]
        overlap = jnp.sum(live[1:] & live[:-1], axis=1, dtype=jnp.int32)
        return jnp.concatenate([jnp.zeros((1,), jnp.int32), overlap])
    if method != "scan":
        raise ValueError(f"unknown overlap method {method!r}")

    def one_window(prev, w):
        cur = jnp.zeros((ip_cap + 1,), jnp.bool_)
        cur = cur.at[jnp.where(win == w, r_src, ip_cap)].set(True)
        cur = cur.at[jnp.where(win == w, r_dst, ip_cap)].set(True)
        cur = cur[:ip_cap]
        return cur, jnp.sum(prev & cur, dtype=jnp.int32)

    _, overlap = jax.lax.scan(
        one_window, jnp.zeros((ip_cap,), jnp.bool_),
        jnp.arange(nw, dtype=jnp.int32),
    )
    return overlap


def cross_window_ip_overlap_naive(
    t: Table, n_windows: int, backend: str = "auto"
) -> jnp.ndarray:
    """Pre-plan overlap: distinct (window, ip) pairs (one group-by over both
    endpoints), then a semi-join of (w, ip) against (w'+1, ip) — which
    re-sorts the rows the group-by just sorted — then one histogram dispatch
    to count members per window.  A/B baseline for the plan path.

    Window ids >= n_windows are dropped by the final histogram (identical to
    the plan path).  A *negative* window id would leak into ``overlap[0]``
    here via the w+1 shift, violating the documented overlap[0] == 0
    contract — the plan path drops it instead; every in-repo caller clips
    window ids upstream, so the two paths agree on all reachable inputs."""
    valid = t.valid_mask()
    win2 = jnp.concatenate([t["win"], t["win"]])
    ip2 = jnp.concatenate([t["src"], t["dst"]])
    mask2 = jnp.concatenate([valid, valid])
    wip = groupby_aggregate([win2, ip2], None, valid_mask=mask2)
    member = semi_join(
        [wip.keys[0], wip.keys[1]],
        [wip.keys[0] + 1, wip.keys[1]],
        left_n_valid=wip.n_groups,
        right_n_valid=wip.n_groups,
    )
    counts = histogram(
        jnp.where(member, wip.keys[0], -1), n_windows, backend=backend
    )
    return counts.astype(jnp.int32)


def _window_activity(t: Table, n_windows: int, ip_bins: int, backend: str):
    """Per-window source-activity histogram: every window through the Pallas
    kernel in ONE dispatch (hashed ip -> bin sketch, exact per bin)."""
    valid = t.valid_mask()
    w = packet_weights(t)
    act_ids = jnp.where(
        valid, (mix32(t["src"]) % jnp.uint32(ip_bins)).astype(jnp.int32), -1
    )
    return windowed_histogram(
        t["win"], act_ids, n_windows, ip_bins,
        weights=jnp.where(valid, w, 0).astype(jnp.float32), backend=backend,
    )


def analyze(
    t: Table,
    *,
    n_windows: int,
    ip_bins: int,
    k: int,
    backend: str = "auto",
    use_plan: bool = True,
    windowed_method: str = "csr",
    fused_epilogue: bool = False,
    plans=None,
) -> ChallengeResults:
    """Every challenge statistic in one jit-able call.

    Sort-once query planning (DESIGN.md §2.3): the whole analyze phase runs
    off THREE sorts — one packed src-leading (src, dst) sort, one mirrored
    dst-leading sort, and the half-domain concat sort of ``unique_ips``.
    Scalars, vector queries, fan-out/fan-in, top-k, the windowed suite and
    the cross-window overlap all derive from that shared ``SortedEdges``
    pair + sorted IP domain with zero additional sorts (asserted on the
    lowered HLO in tests/test_plan.py).  The windowed suite defaults to the
    sparse CSR formulation (DESIGN.md §2.4, O(nnz) peak memory);
    ``windowed_method="grid"`` keeps the dense-scatter A/B baseline
    (O(n_windows × capacity) peak).  ``use_plan=False`` runs the pre-plan
    formulation — ~10 independent group-by sorts that XLA CSE can only
    partially dedupe — as the A/B baseline; all paths return bit-identical
    results.

    ``fused_epilogue=True`` routes the analyze phase's two remaining
    scatter/gather chains — the windowed suite's per-window slice select
    and the top-k pre-mask — through the kernel lane's fused gate /
    valid-mask epilogues (DESIGN.md §2.9).  Bit-identical to the unfused
    path (which stays the A/B baseline), same 3-sort budget; requires the
    CSR windowed method.

    ``plans`` is the plan pair of ``t`` when the caller already holds it
    (:func:`_analyze_and_plans`); the plan path then sorts only for
    ``unique_ips``.  The graph kernels are a phase of their own
    (:func:`graph_plan`, :func:`graph_kernel`), not part of this program.
    """
    if not use_plan:
        if fused_epilogue:
            raise ValueError(
                "fused_epilogue=True requires the plan path (use_plan=True):"
                " the epilogues fuse into the plan's shared reductions"
            )
        return _analyze_naive(
            t, n_windows=n_windows, ip_bins=ip_bins, k=k, backend=backend
        )
    # jax.named_scope labels each query family in the ops' metadata
    # (analyze/plan, analyze/groups, ...) and changes no computation
    with jax.named_scope("analyze"):
        with jax.named_scope("plan"):
            plans = table_plans(t) if plans is None else plans
            plan_src, plan_dst = plans
            ips = unique_ips(t)
        with jax.named_scope("groups"):
            links = link_groups(plan_src)
            per_src = lead_groups(plan_src)
            per_dst = lead_groups(plan_dst)
            fanout = lead_fanout(plan_src)
            fanin = lead_fanout(plan_dst)

        with jax.named_scope("scalars"):
            scalars = scalar_queries_from_plans(
                t, plan_src, plan_dst, ips, links=links, per_src=per_src,
                per_dst=per_dst, fanout=fanout, fanin=fanin,
            )
        with jax.named_scope("groups"):
            unique_sources = unique_lead(plan_src)
            unique_destinations = unique_lead(plan_dst)
        with jax.named_scope("topk"):
            top = top_links_from_plan(
                plan_src, k, links, fused=fused_epilogue, backend=backend
            )
        with jax.named_scope("windowed"):
            windowed = windowed_queries(
                t, 1, n_windows, ts_col="win", t0=0, plans=plans,
                method=windowed_method, fused=fused_epilogue, backend=backend)
        with jax.named_scope("activity"):
            activity = _window_activity(t, n_windows, ip_bins, backend)
        with jax.named_scope("overlap"):
            overlap = cross_window_ip_overlap(
                t, n_windows, ips=ips,
                method="scan" if windowed_method == "csr" else "grid",
            )

    return ChallengeResults(
        scalars=scalars,
        links=links,
        per_source=per_src,
        per_destination=per_dst,
        source_fanout=fanout,
        destination_fanin=fanin,
        unique_sources=unique_sources,
        unique_destinations=unique_destinations,
        top=top,
        windowed=windowed,
        window_activity=activity,
        window_ip_overlap=overlap,
    )


def _analyze_naive(
    t: Table, *, n_windows: int, ip_bins: int, k: int, backend: str
) -> ChallengeResults:
    """Pre-plan analyze: one group-by sort per query family, relying on XLA
    CSE to dedupe what it structurally can."""
    with jax.named_scope("analyze"):
        with jax.named_scope("groups"):
            w = packet_weights(t)
            links = traffic_matrix(t)
            per_src = groupby_aggregate(
                [t["src"]], {"packets": (w, "sum")}, n_valid=t.n_valid
            )
            per_dst = groupby_aggregate(
                [t["dst"]], {"packets": (w, "sum")}, n_valid=t.n_valid
            )
            fanout = groupby_aggregate([links.keys[0]], None,
                                       n_valid=links.n_groups)
            fanin = groupby_aggregate([links.keys[1]], None,
                                      n_valid=links.n_groups)
        with jax.named_scope("scalars"):
            scalars = run_all_queries_naive(t)
        with jax.named_scope("groups"):
            unique_sources = unique(t["src"], n_valid=t.n_valid)
            unique_destinations = unique(t["dst"], n_valid=t.n_valid)
        with jax.named_scope("topk"):
            top = top_links(t, k)
        with jax.named_scope("windowed"):
            windowed = windowed_queries_naive(t, 1, n_windows, ts_col="win",
                                              t0=0)
        with jax.named_scope("activity"):
            activity = _window_activity(t, n_windows, ip_bins, backend)
        with jax.named_scope("overlap"):
            overlap = cross_window_ip_overlap_naive(t, n_windows, backend)

    return ChallengeResults(
        scalars=scalars,
        links=links,
        per_source=per_src,
        per_destination=per_dst,
        source_fanout=fanout,
        destination_fanin=fanin,
        unique_sources=unique_sources,
        unique_destinations=unique_destinations,
        top=top,
        windowed=windowed,
        window_activity=activity,
        window_ip_overlap=overlap,
    )


def analyze_peak_buffer_bytes(
    capacity: int,
    *,
    windowed_method: str,
    n_windows: int,
    ip_bins: int = 1024,
    k: int = 10,
    n_valid: Optional[int] = None,
) -> float:
    """Compiled-HLO peak-buffer estimate of :func:`analyze` at a capacity.

    Compile-only (nothing executes): lowers ``analyze`` over a zero table
    and feeds the post-optimization HLO to
    ``launch/hloanalysis.peak_buffer_bytes``.  The ONE definition of the
    memory-gate harness — ``benchmarks/bench_graphblas.py`` (the CI smoke)
    and ``tests/test_memory_budget.py`` (the pinned scale-17 gate) both
    call it, so the two gates measure the same program.
    """
    from ..launch.hloanalysis import peak_buffer_bytes

    t = Table.from_dict(
        {c: np.zeros(capacity, np.int32) for c in ("src", "dst", "win")},
        n_valid=capacity - 1 if n_valid is None else n_valid,
    )
    f = jax.jit(lambda t: analyze(
        t, n_windows=n_windows, ip_bins=ip_bins, k=k, backend="xla",
        windowed_method=windowed_method,
    ))
    return peak_buffer_bytes(f.lower(t).compile().as_text())


# ---------------------------------------------------------------------------
# phase: graph
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrafficGraph:
    """The anonymized traffic graph the graph kernels run on.

    ``csr_src`` is A (rows by source: out-links, weighted by packets),
    ``csr_dst`` its transpose, ``keys_src``/``keys_dst`` their entries' row
    keys (``entry_row_key(0)``, a scatter and a prefix sum each kernel
    step would repeat otherwise); ``n_live`` counts the vertices (ids are
    ``[0, n_live)``); ``source`` is the BFS source."""

    csr_src: object
    csr_dst: object
    keys_src: jnp.ndarray
    keys_dst: jnp.ndarray
    n_live: jnp.ndarray
    source: jnp.ndarray


jax.tree_util.register_dataclass(
    TrafficGraph,
    data_fields=[f.name for f in dataclasses.fields(TrafficGraph)],
    meta_fields=[],
)


def graph_plan(t: Table, n_live, source=-1, plans=None) -> TrafficGraph:
    """The CSR pair of the anonymized table, off the two plan sorts and
    nothing else (``n_live`` is the anonymization's distinct-IP count), or
    off ``plans``, the pair ``analyze`` already sorted, with no sort at
    all.  ``source`` is the BFS source, or -1 for the source of the table's
    first row (the capture's first packet)."""
    with jax.named_scope("graph"):
        if plans is None:
            with jax.named_scope("plan"):
                plans = table_plans(t)
        with jax.named_scope("csr"):
            csr_src, csr_dst = table_csrs(t, plans)
            keys_src = csr_src.entry_row_key(0)
            keys_dst = csr_dst.entry_row_key(0)
    source = jnp.where(source < 0, t["src"][0], source)
    return TrafficGraph(csr_src=csr_src, csr_dst=csr_dst, keys_src=keys_src,
                        keys_dst=keys_dst,
                        n_live=jnp.asarray(n_live, jnp.int32),
                        source=source.astype(jnp.int32))


def graph_kernel(g: TrafficGraph, *, kernel: str, n_vertices: int,
                 backend: str = "auto", **pagerank_kw):
    """One graph kernel over ``g`` (core/algorithms.py): ``bfs`` levels
    from ``g.source`` over out-links, ``wcc`` min-id labels of the weak
    components, ``pagerank`` (``pagerank_kw``: ``weighted``) or
    ``triangles``."""
    with jax.named_scope("graph"), jax.named_scope(kernel):
        if kernel == "bfs":
            return algo.bfs_levels(g.csr_src, g.source, n_vertices,
                                   n_live=g.n_live, backend=backend,
                                   row_keys=g.keys_src)
        if kernel == "wcc":
            return algo.connected_components(
                g.csr_src, n_vertices, csr_t=g.csr_dst, n_live=g.n_live,
                backend=backend, row_keys=g.keys_src, row_keys_t=g.keys_dst)
        if kernel == "pagerank":
            return algo.pagerank(g.csr_src, n_vertices, n_live=g.n_live,
                                 backend=backend, row_keys=g.keys_src,
                                 **pagerank_kw)
        if kernel == "triangles":
            return algo.triangle_counts(g.csr_src, n_vertices,
                                        backend=backend)
    raise ValueError(f"unknown graph kernel {kernel!r}")


def _graph_statics(a: Analytics, kernel: str) -> dict:
    """The statics that shape ``kernel``'s program, and no others, so a
    PageRank setting compiles no new BFS."""
    return dict(weighted=a.weighted) if kernel == "pagerank" else {}


# ---------------------------------------------------------------------------
# the orchestrator
# ---------------------------------------------------------------------------

def _block(x):
    jax.block_until_ready(x)
    return x


def _dispatch_and_sync(fn, *args):
    """Run one jitted phase program to completion under two child spans:
    ``dispatch`` until the call returns (trace, lower, compile or load,
    enqueue) and ``sync`` while the host waits for the device."""
    with obs_span("dispatch"):
        out = fn(*args)
    with obs_span("sync"):
        return _block(out)


def _analyze_and_plans(t, **kw):
    """``analyze`` that also hands back its plan pair, for a pass whose
    graph phase then builds its CSR pair with no sort of its own."""
    with jax.named_scope("analyze"), jax.named_scope("plan"):
        plans = table_plans(t)
    return analyze(t, plans=plans, **kw), plans


def _build(s, d, wn, nv):
    with jax.named_scope("build_table"):
        table = build_table(s, d, wn, nv)  # build once; A_t groups it
    with jax.named_scope("traffic_matrix"):
        return table, traffic_matrix(table)


def _fused(s, d, wn, nv, k_, *, method, rounds, **kw):
    with jax.named_scope("build_table"):
        t = build_table(s, d, wn, nv)
    return analyze(anonymize(t, k_, method=method, rounds=rounds).table, **kw)


# Phase programs are built once per process, keyed by the phase function and
# the static arguments that shape its trace; array shapes are left to each
# jitted callable's own cache.  A jax.jit made per call misses JAX's
# in-memory executable cache, which is keyed by function identity, so every
# pass would trace, lower and load each program again (the stream engine
# caches its programs the same way).  Keying by the function as well gives a
# replaced ``anonymize`` or ``analyze`` a program of its own.

@functools.lru_cache(maxsize=None)
def _jitted(fn, donate_argnums=(), **statics):
    return jax.jit(functools.partial(fn, **statics),
                   donate_argnums=donate_argnums)


def run_challenge(
    cfg: ChallengeConfig, key: Optional[jax.Array] = None
) -> ChallengeRun:
    """Run read -> build -> anonymize -> analyze and/or graph, timing each
    phase."""
    a = cfg.analytics
    # refuse a vertex domain the float32 carriers cannot hold before the run
    n_vertices = graph_vertices(cfg.table_capacity) if a.kernels else 0
    if cfg.distributed:
        _mesh_device_count()  # refuse before the run, not after it
    if key is None:
        key = jax.random.key(cfg.seed)
    workdir = cfg.workdir or tempfile.mkdtemp(prefix="netsense_challenge_")
    os.makedirs(workdir, exist_ok=True)
    kw = dict(n_windows=cfg.n_windows, ip_bins=cfg.ip_bins, k=cfg.top_k,
              backend=cfg.backend, fused_epilogue=cfg.fused_epilogue)
    build_fn = _jitted(_build)
    anon_fn = _jitted(anonymize, method=cfg.method, rounds=cfg.rounds)
    analyze_fn = None
    if a.queries:  # with kernels too, the graph phase reuses its plan pair
        analyze_fn = _jitted(_analyze_and_plans if a.kernels else analyze,
                             **kw)
    plan_fn = _jitted(graph_plan)
    kernel_fns = {k: _jitted(graph_kernel, kernel=k, n_vertices=n_vertices,
                             backend=cfg.backend, **_graph_statics(a, k))
                  for k in a.kernels}

    # -1: the first packet's source; an operand either way
    source = np.int32(-1 if a.bfs_source is None else a.bfs_source)

    def analyze_phase(table, span=_dispatch_and_sync):
        """(results, the plan pair when the graph phase reuses it)"""
        out = span(analyze_fn, table)
        return out if a.kernels else (out, None)

    def graph_phase(anon, plans, span=_dispatch_and_sync):
        with obs_span("plan"):
            g = span(plan_fn, anon.table, anon.n_ips, source, plans)
        out = {}
        for k, fn in kernel_fns.items():
            with obs_span(k):
                out[k] = span(fn, g)
                if k != "triangles":  # the one kernel with no fixed point
                    counter_event(f"graph.{k}.iterations",
                                  int(out[k].iterations))
                    counter_event(f"graph.{k}.converged",
                                  int(out[k].converged))
        return GraphRun(kernels=out, n_live=g.n_live, source=g.source)

    # Phase timing is span-based (obs/trace.py): each wall below is a span's
    # duration over the same perf_counter clock the old inline timers used,
    # and ChallengePhaseTimings is now a *derived view* of those spans —
    # timings_from_spans reconstructs it bit-identically from the exported
    # JSONL (gated in tests/test_obs.py).
    with obs_span("challenge", scale=cfg.scale, n_packets=cfg.packets,
                  fmt=cfg.fmt, fused=cfg.fused, warm=cfg.warm) as sp_chal:
        # ---- read (host I/O) ----
        with obs_span("read") as sp_read:
            capture = read_phase(cfg, workdir)

        with obs_span("build_host") as sp_build_host:
            src, dst, win, n = build_columns(capture, cfg)
            # window ids + padding (one-off host work, folded into build_s)
        sp_chal.attrs["n_packets"] = n  # live rows, not the configured count

        # ---- warm pass: trace + compile every phase so the timed walls
        # below measure steady-state execution, matching the paper's
        # protocol of excluding one-time costs (recorded as compile_s; a
        # config already run in this process finds its programs cached) ----
        sp_compile = None
        if cfg.warm:
            with obs_span("compile") as sp_compile:
                wt, _ = _block(build_fn(src, dst, win, n))
                wa = _block(anon_fn(wt, key))
                warm = lambda fn, *x: _block(fn(*x))
                plans = None
                if analyze_fn is not None:
                    _, plans = analyze_phase(wa.table, span=warm)
                if kernel_fns:
                    graph_phase(wa, plans, span=warm)

        # ---- build (windows + transfer + A_t group-by) ----
        with obs_span("build_device") as sp_build_dev:
            table, _links = _dispatch_and_sync(build_fn, src, dst, win, n)

        # ---- anonymize ----
        with obs_span("anonymize") as sp_anon:
            anon = _dispatch_and_sync(anon_fn, table, key)

        # ---- analyze ----
        results = plans = sp_analyze = None
        if analyze_fn is not None:
            with obs_span("analyze") as sp_analyze:
                results, plans = analyze_phase(anon.table)

        # ---- graph: the CSR pair (and the plan pair unless analyze sorted
        # it), then each kernel's own program ----
        graph = sp_graph = None
        if kernel_fns:
            with obs_span("graph") as sp_graph:
                graph = graph_phase(anon, plans)

        wall = lambda sp: None if sp is None else sp.duration_s
        timings = ChallengePhaseTimings(
            n_packets=n,
            read_s=sp_read.duration_s,
            build_s=sp_build_host.duration_s + sp_build_dev.duration_s,
            anonymize_s=sp_anon.duration_s,
            analyze_s=wall(sp_analyze),
            graph_s=wall(sp_graph),
            compile_s=wall(sp_compile),
        )

        if cfg.distributed:
            results = dataclasses.replace(
                results, scalars=distributed_scalar_queries(anon.table)
            )

        if cfg.fused:
            timings.fused_s = _time_fused(cfg, src, dst, win, n, key, kw)

    return ChallengeRun(results=results, timings=timings, capture=capture,
                        config=cfg, anon_table=anon.table, graph=graph)


def _time_fused(cfg, src, dst, win, n, key, kw) -> float:
    """build+anonymize+analyze as ONE jitted, buffer-donated program."""
    # donating the column buffers lets XLA reuse them for the sort scratch;
    # CPU ignores donation, so only request it off-CPU (avoids the warning).
    donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
    fn = _jitted(_fused, donate, method=cfg.method, rounds=cfg.rounds, **kw)
    _block(fn(src, dst, win, n, key))  # compile + warm
    src2, dst2, win2 = np.copy(src), np.copy(dst), np.copy(win)
    with obs_span("fused") as sp:
        _block(fn(src2, dst2, win2, n, key))
    return sp.duration_s


def _mesh_device_count() -> int:
    """Local device count for the shard_map path; refuses fewer than two."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"distributed scalar queries need >= 2 devices, found {n_dev} "
            f"({jax.devices()[0].platform}); run without --distributed"
        )
    return n_dev


@functools.lru_cache(maxsize=None)
def _distributed_suite(n_dev: int):
    """The jitted shard_map scalar suite over ``n_dev`` devices, built once
    so that repeated snapshots reuse one executable."""
    from jax.sharding import PartitionSpec as P

    from ..dist.relational import distributed_queries
    from ..launch.mesh import make_analytics_mesh

    def fn(src, dst, w, nv):
        # per-shard validity: rows are globally [0, n_valid) — recompute
        # locally
        shard = jax.lax.axis_index("rows")
        local = src.shape[0]
        local_nv = jnp.clip(nv - shard * local, 0, local)
        tt = Table(columns={"src": src, "dst": dst, "n_packets": w},
                   n_valid=local_nv)
        return distributed_queries(tt, "rows")

    return jax.jit(jax.shard_map(
        fn, mesh=make_analytics_mesh(n_dev),
        in_specs=(P("rows"), P("rows"), P("rows"), P()),
        out_specs=P(),
    ))


def distributed_scalar_queries(t: Table) -> QueryResults:
    """Scalar suite via the shard_map path over all local devices.

    Accepts any packet-shaped table (``src``, ``dst``, optional
    ``n_packets`` weights) — the streaming engine reuses this to merge its
    accumulated link-table state through ``repro.dist`` (weighted links are
    query-equivalent to the packets they summarize).

    Raises ``RuntimeError`` with fewer than two devices: a one-shard
    "distributed" run would silently be the single-device path.
    """
    n_dev = _mesh_device_count()
    cap = t.capacity
    pad_to = -(-cap // n_dev) * n_dev
    grow = lambda a: jnp.pad(a, (0, pad_to - cap))
    out = _distributed_suite(n_dev)(
        grow(t["src"]), grow(t["dst"]), grow(packet_weights(t)), t.n_valid
    )
    overflow = int(out["overflow"])
    if overflow:
        # the exchange contract: overflow is reported, never silent — the
        # distinct/max statistics may undercount, so refuse to return them
        raise RuntimeError(
            f"distributed query exchange overflowed {overflow} rows "
            "(skewed keys); rerun with a larger overflow_factor or "
            "distributed=False"
        )
    return QueryResults(**{
        f.name: out[f.name] for f in dataclasses.fields(QueryResults)
    })
