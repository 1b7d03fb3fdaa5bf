"""Window driver of the batch challenge: whole passes of ``run_challenge``.

Set-up writes the capture drawn from the seed as the plq file the pipeline
reads, and runs one whole pass, which compiles or loads every program of a
pass.  The window then runs passes back to back until ``seconds`` have
passed; it ends when the last pass ends.  ``challenge_packets_per_s`` is all
packets of those passes over the window's wall.  With ``trace`` one more
pass runs under the profiler after the window.

The check: the anonymized table is a bijection of the capture's IP domain
applied to every row, its window column is the capture's, every pass's
scalars equal the reference, and one pass drawn from the seed has every
output of ``analyze`` compared entry by entry.
"""
from __future__ import annotations

import time

import numpy as np

from bench import answers, reference, trace_reduce
from bench.harness import DriverResult, memory_peak_bytes
from bench.traffic import generate

PHASES = ("read", "build_host", "build_device", "anonymize", "analyze")


def _last_seq(tracer) -> int:
    # a span's seq is taken when it opens, so the last record is not the
    # newest: a parent closes after its children
    return max((r["seq"] for r in tracer.records()), default=-1)


def _phase_spans(records, after: int):
    """Per-pass phase spans of ``run_challenge`` recorded after ``after``."""
    return [r for r in records
            if r["kind"] == "span" and r["seq"] > after
            and r["parent"] == "challenge" and r["name"] in PHASES]


def run(ctx) -> DriverResult:
    import jax
    from jax.profiler import TraceAnnotation
    from repro.challenge.pipeline import ChallengeConfig, run_challenge
    from repro.data.plq import write_plq
    from repro.obs import get_tracer

    c, t = ctx.config, ctx.traffic
    cfg = ChallengeConfig(
        scale=t["scale"], n_packets=t["n_packets"],
        capacity=c["table_capacity"], n_windows=c["n_windows"],
        ip_bins=c["ip_bins"], top_k=c["top_k"], method=c["method"],
        rounds=c["rounds"], warm=False, seed=ctx.seed, fmt=c["fmt"],
        backend=c["backend"], workdir=str(ctx.workdir))
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
    passes, sample, pass_scalars, pass_walls = 0, None, [], []
    t0 = time.perf_counter()
    while True:
        tp = time.perf_counter()
        run_ = run_challenge(cfg)
        pass_walls.append(time.perf_counter() - tp)
        passes += 1
        pass_scalars.append(run_.results.scalars)
        if rng.random() * passes < 1.0:
            sample = run_
        del run_
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    wall = time.perf_counter() - t0
    ctx.end_window()

    spans = {}
    for r in _phase_spans(tracer.records(), seq0):
        spans.setdefault(r["name"], []).append(r["duration_s"])
    summary = None
    if ctx.trace:
        seq1 = _last_seq(tracer)
        with trace_reduce.profile(str(ctx.trace_dir)):
            with TraceAnnotation(trace_reduce.WINDOW):
                clock0 = time.perf_counter()
                run_challenge(cfg)
        traced = [(r["t_mono"], r["t_mono"] + r["duration_s"], r["name"])
                  for r in _phase_spans(tracer.records(), seq1)]
        summary = trace_reduce.reduce_dir(str(ctx.trace_dir),
                                          program_spans=traced, clock0=clock0)
    peak = memory_peak_bytes(jax.devices()[:1])

    got = answers.challenge(sample.results)
    at = sample.anon_table
    n = t["n_packets"]
    anon_src = np.asarray(at["src"])[:n].astype(np.int64)
    anon_dst = np.asarray(at["dst"])[:n].astype(np.int64)
    anon_win = np.asarray(at["win"])[:n].astype(np.int64)
    n_valid = int(at.n_valid)
    all_scalars = [answers.scalars(s) for s in pass_scalars]
    del sample, at, pass_scalars

    src = cols["src"].astype(np.int64)
    dst = cols["dst"].astype(np.int64)
    win = reference.window_ids(cols["ts"], c["n_windows"])
    ref = reference.challenge_answers(
        anon_src, anon_dst, win, np.ones(n, np.int64),
        n_windows=c["n_windows"], ip_bins=c["ip_bins"], k=c["top_k"])
    wrong = reference.compare_challenge(ref, got)
    per_pass = [reference.compare_scalars(ref["scalars"], s)
                for s in all_scalars]
    wrong["scalars_wrong"] += sum(per_pass)
    checks = {
        "anonymize_wrong": reference.anonymize_wrong(src, dst, anon_src,
                                                     anon_dst),
        "build_wrong": reference.wrong(win, anon_win) + int(n_valid != n),
        **wrong,
    }
    failed = sum(1 for x in per_pass if x)
    if any(checks.values()) and not failed:
        failed = 1
    return DriverResult(
        e2e={"setup_s": ctx.setup_s,
             "challenge_packets_per_s": passes * n / wall},
        obs={"spans": spans, "trace": summary,
             "window": {"wall_s": wall, "passes": passes,
                        "packets": passes * n, "pass_walls": pass_walls}},
        checks={k: (v, 0) for k, v in checks.items()},
        attempted=passes, failed=failed, memory_peak_bytes=peak)
