"""Window driver of the Graphalytics cell: whole passes of ``run_challenge``
whose analytics are the graph kernels alone.

The configuration is built first, before the capture is drawn or the
device is touched, so a program without the graph phase fails at once.
Set-up then writes the capture drawn from the seed as the plq file the
pipeline reads and runs one whole pass, which compiles or loads every
program of a pass.  The window runs passes back to back until ``seconds``
have passed and ends when the last pass ends; ``challenge_packets_per_s``
is all packets of those passes over the window's wall.  With ``trace`` one
more pass runs under the profiler after the window.

Every pass replays the one capture under the one anonymization key of the
seed, as the batch cell does, and runs BFS from the anonymized source of
the capture's first packet.  So every pass of a run does the same work,
and the step counts (BFS, WCC) move from seed to seed only.

The check: the anonymized table is a bijection of the capture's IP domain
applied to every row, its window column is the capture's, every kernel of
every pass converged, and for one pass drawn from the seed the BFS levels
and WCC labels equal :mod:`bench.graph_reference`'s over that pass's
anonymized links, and PageRank lies within its L1 limit of the reference
run under the configuration's stopping rule and stopped within a step of
where it stops.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from bench import graph_reference, reference, trace_reduce
from bench.harness import DriverResult, memory_peak_bytes
from bench.traffic import generate

PHASES = ("read", "build_host", "build_device", "anonymize", "graph")


def _last_seq(tracer) -> int:
    return max((r["seq"] for r in tracer.records()), default=-1)


def _spans(records, after: int, kernels):
    """The per-pass spans recorded after ``after``, by name: each phase of
    ``run_challenge``, and ``graph/<part>`` for the graph phase's plan and
    kernels."""
    parts = ("plan",) + tuple(kernels)
    out = {}
    for r in records:
        if r["kind"] != "span" or r["seq"] <= after:
            continue
        if r["parent"] == "challenge" and r["name"] in PHASES:
            out.setdefault(r["name"], []).append(r)
        elif r["parent"] == "challenge/graph" and r["name"] in parts:
            out.setdefault(f"graph/{r['name']}", []).append(r)
    return out


def _sync_walls(records, after: int):
    """Each device phase's ``sync`` child recorded after ``after``, by
    ``<phase>/sync``: the host's wait on the device, so a slow pass shows
    whether the device or the host held it."""
    out = {}
    for r in records:
        if (r["kind"] == "span" and r["seq"] > after and r["name"] == "sync"
                and r["parent"].startswith("challenge/")):
            name = r["parent"][len("challenge/"):] + "/sync"
            out.setdefault(name, []).append(r["duration_s"])
    return out


def _iterations(records, after: int, kernels):
    """Each kernel's ``graph.<kernel>.iterations`` counters recorded after
    ``after``, one a pass, in order."""
    names = {f"graph.{k}.iterations": k for k in kernels}
    out = {k: [] for k in kernels}
    for r in sorted(records, key=lambda r: r["seq"]):
        if r["kind"] == "counter" and r["seq"] > after and r["name"] in names:
            out[names[r["name"]]].append(r["value"])
    return out


def config(ctx):
    """The pipeline's configuration of the cell."""
    from repro.challenge.pipeline import Analytics, ChallengeConfig

    c, t = ctx.config, ctx.traffic
    return ChallengeConfig(
        scale=t["scale"], n_packets=t["n_packets"],
        capacity=c["table_capacity"], n_windows=c["n_windows"],
        method=c["method"], rounds=c["rounds"], warm=False, seed=ctx.seed,
        fmt=c["fmt"], backend=c["backend"], workdir=str(ctx.workdir),
        analytics=Analytics(queries=False, kernels=tuple(c["kernels"]),
                            weighted=c["pagerank"]["weighted"]))


def outputs(run) -> dict:
    """One pass's graph outputs, on the host, in the layout
    :func:`bench.graph_reference.checks` reads."""
    k = run.graph.kernels
    got = {}
    if "bfs" in k:
        got["bfs_levels"] = np.asarray(k["bfs"].levels)
    if "wcc" in k:
        got["wcc_labels"] = np.asarray(k["wcc"].labels)
    if "pagerank" in k:
        got["pagerank_ranks"] = np.asarray(k["pagerank"].ranks)
        got["pagerank_steps"] = int(k["pagerank"].iterations)
    return got


def run(ctx) -> DriverResult:
    cfg = config(ctx)
    import jax
    from jax.profiler import TraceAnnotation
    from repro.challenge.pipeline import run_challenge
    from repro.data.plq import write_plq
    from repro.obs import get_tracer

    c, t = ctx.config, ctx.traffic
    pr = c["pagerank"]
    kernels = tuple(c["kernels"])
    ctx.step("start")
    cols = generate(t, ctx.seed)
    ctx.step("capture")
    write_plq(cfg.capture_path(cfg.workdir), cols,
              row_group_size=t["row_group_size"])
    ctx.step("write")
    with TraceAnnotation("bench.warm"):
        run_challenge(cfg)
    ctx.step("warm_pass")
    tracer = get_tracer()
    seq0 = _last_seq(tracer)
    rng = np.random.default_rng(ctx.seed)

    ctx.mark_setup()
    passes, sample, pass_walls, flags = 0, None, [], []
    t0 = time.perf_counter()
    while True:
        tp = time.perf_counter()
        run_ = run_challenge(cfg)
        pass_walls.append(time.perf_counter() - tp)
        passes += 1
        flags.append([r.converged for r in run_.graph.kernels.values()])
        if rng.random() * passes < 1.0:
            sample = run_
        del run_
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    wall = time.perf_counter() - t0
    ctx.end_window()

    records = tracer.records()
    spans = {name: [r["duration_s"] for r in recs]
             for name, recs in _spans(records, seq0, kernels).items()}
    iterations = _iterations(records, seq0, kernels)
    # each pass's phase walls and device waits, to tell which phase holds a
    # slow pass and whether the device or the host held it
    print("phase_walls " + json.dumps({**spans,
                                        **_sync_walls(records, seq0)}),
          file=sys.stderr)
    summary = None
    if ctx.trace:
        seq1 = _last_seq(tracer)
        with trace_reduce.profile(str(ctx.trace_dir)):
            with TraceAnnotation(trace_reduce.WINDOW):
                clock0 = time.perf_counter()
                run_challenge(cfg)
        traced = [(r["t_mono"], r["t_mono"] + r["duration_s"], name)
                  for name, recs in _spans(tracer.records(), seq1,
                                           kernels).items() for r in recs]
        summary = trace_reduce.reduce_dir(str(ctx.trace_dir),
                                          program_spans=traced, clock0=clock0)
    peak = memory_peak_bytes(jax.devices()[:1])

    got = outputs(sample)
    got["unconverged"] = sum(not bool(x) for f in flags for x in f)
    at = sample.anon_table
    n = t["n_packets"]
    anon_src = np.asarray(at["src"])[:n].astype(np.int64)
    anon_dst = np.asarray(at["dst"])[:n].astype(np.int64)
    anon_win = np.asarray(at["win"])[:n].astype(np.int64)
    n_valid = int(at.n_valid)
    del sample, at

    src = cols["src"].astype(np.int64)
    dst = cols["dst"].astype(np.int64)
    win = reference.window_ids(cols["ts"], c["n_windows"])
    n_vertices = len(np.unique(np.concatenate([src, dst])))
    u, v = graph_reference.links(anon_src, anon_dst)
    checks = {
        "anonymize_wrong": reference.anonymize_wrong(src, dst, anon_src,
                                                     anon_dst),
        "build_wrong": reference.wrong(win, anon_win) + int(n_valid != n),
        # the BFS source: the anonymized source of the first packet
        **graph_reference.checks(got, u, v, n_vertices, int(anon_src[0]),
                                 damping=pr["damping"], tol=pr["tol"],
                                 max_steps=pr["max_iters"]),
    }
    limits = {k: graph_reference.LIMITS.get(k, 0) for k in checks}
    failed = int(any(checks[k] > limits[k] for k in checks))
    return DriverResult(
        e2e={"setup_s": ctx.setup_s,
             "challenge_packets_per_s": passes * n / wall},
        obs={"spans": spans, "iterations": iterations, "trace": summary,
             "window": {"wall_s": wall, "passes": passes,
                        "packets": passes * n, "pass_walls": pass_walls}},
        checks={k: (v_, limits[k]) for k, v_ in checks.items()},
        attempted=passes, failed=failed, memory_peak_bytes=peak)
