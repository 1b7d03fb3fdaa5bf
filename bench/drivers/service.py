"""Window driver of the sensor service: ``stream_plq`` passes and snapshots.

Set-up writes the capture drawn from the seed as a plq file whose row groups
are the micro-batches, and folds one whole pass and takes one snapshot, which
compiles or loads the fold and the snapshot and fills the state with the
capture's links.  The window then replays the capture as a closed loop, one
snapshot after each pass, until ``seconds`` have passed; it ends when the
last snapshot ends.  ``ingest_packets_per_s`` (exact tier) or
``ingest_packets_per_s.sketch`` is every packet folded in the window over
its wall, snapshots included; ``snapshot_s`` the mean wall of
the window's snapshots.  With ``trace`` the window times each micro-batch's
phases (``time_phases``), and one more pass and snapshot run under the
profiler after it.

The check, exact tier: the fold state after every pass (dictionary, links,
activity, counters, overflow 0), every snapshot's scalars, and every output
of one snapshot drawn from the seed, against the reference over every packet
folded up to it.  Sketch tier: every snapshot's estimates against the
bounds the configuration states.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import answers, reference, trace_reduce
from bench.harness import DriverResult, memory_peak_bytes
from bench.traffic import generate

# each tier's ingest rate is a metric of its own, so that the host-bound
# sketch cell's spread does not set the exact cell's bound
INGEST = {"exact": "ingest_packets_per_s",
          "sketch": "ingest_packets_per_s.sketch"}


def _engine(ctx):
    from repro.core.sketch import SketchConfig
    from repro.stream.engine import StreamConfig, StreamEngine

    c = ctx.config
    sketch = None
    if c["tier"] != "exact":
        sketch = SketchConfig(cms_depth=c["cms_depth"],
                              cms_width=c["cms_width"], hll_p=c["hll_p"],
                              heavy_capacity=c["heavy_capacity"],
                              seed=c["hash_salt"], hll_sigma=c["hll_sigma"])
    return StreamEngine(StreamConfig(
        batch_capacity=c["batch_capacity"], link_capacity=c["link_capacity"],
        ip_capacity=c["ip_capacity"], n_windows=c["n_windows"],
        ip_bins=c["ip_bins"], top_k=c["top_k"], backend=c["backend"],
        tier=c["tier"], sketch=sketch))


def run(ctx) -> DriverResult:
    import jax
    from jax.profiler import TraceAnnotation
    from repro.challenge.pipeline import window_column
    from repro.data.plq import read_plq, write_plq
    from repro.stream.engine import stream_plq

    c, t = ctx.config, ctx.traffic
    exact = c["tier"] == "exact"
    ctx.step("start")
    cols = generate(t, ctx.seed)
    ctx.step("capture")
    path = str(ctx.workdir / "capture.plq")
    write_plq(path, cols, row_group_size=t["row_group_size"])
    win_full = window_column(read_plq(path, ["ts"])["ts"], c["n_windows"])
    ctx.step("write")
    engine = _engine(ctx)
    ctx.step("engine")
    with TraceAnnotation("bench.warm"):
        stream_plq(engine, path, win_full)
        ctx.step("warm_pass")
        engine.snapshot()
    ctx.step("warm_snapshot")
    passes = 1
    rng = np.random.default_rng(ctx.seed)

    ctx.mark_setup()
    folded, walls, pass_walls, batches = 0, [], [], []
    snaps = []          # (passes so far, scalars or sketch estimates)
    sample = None       # (passes so far, results) of one exact snapshot
    t0 = time.perf_counter()
    while True:
        tp = time.perf_counter()
        timings = stream_plq(engine, path, win_full, time_phases=ctx.trace)
        passes += 1
        folded += sum(b.n_packets for b in timings)
        batches += timings
        ts = time.perf_counter()
        snap = engine.snapshot()
        walls.append(time.perf_counter() - ts)
        pass_walls.append(time.perf_counter() - tp)
        if exact:
            snaps.append((passes, snap.results.scalars))
            if rng.random() * len(snaps) < 1.0:
                # the snapshot hands back the state's own activity buffer,
                # which the next fold donates: keep a host copy
                sample = (passes, dataclasses.replace(
                    snap.results,
                    window_activity=np.asarray(snap.results.window_activity)))
        else:
            snaps.append((passes, answers.sketch(snap.sketch)))
        del snap
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    wall = time.perf_counter() - t0
    ctx.end_window()

    tier = "exact" if exact else "sketch"
    spans = {f"fold.{tier}": [b.update_s for b in batches],
             "batch_prep": [b.prep_s + b.transfer_s for b in batches]}
    summary = None
    if ctx.trace:
        with trace_reduce.profile(str(ctx.trace_dir)):
            with TraceAnnotation(trace_reduce.WINDOW):
                with TraceAnnotation("bench.fold_pass"):
                    stream_plq(engine, path, win_full)
                with TraceAnnotation("bench.snapshot"):
                    snap = engine.snapshot()
        passes += 1
        snaps.append((passes, snap.results.scalars if exact
                       else answers.sketch(snap.sketch)))
        del snap
        summary = trace_reduce.reduce_dir(str(ctx.trace_dir))
    peak = memory_peak_bytes(jax.devices()[:1])

    src = cols["src"].astype(np.int64)
    dst = cols["dst"].astype(np.int64)
    win = reference.window_ids(cols["ts"], c["n_windows"])
    kw = dict(n_windows=c["n_windows"], ip_bins=c["ip_bins"])
    if exact:
        one = reference.stream_state(
            src, dst, win,
            batches_per_pass=-(-t["n_packets"] // t["row_group_size"]), **kw)
        state = answers.stream_state(engine.state)
        sample_passes, sample_got = sample[0], answers.challenge(sample[1])
        got_scalars = [(p, answers.scalars(s)) for p, s in snaps]
        del engine, sample, snaps
        checks = reference.compare_stream_state(
            reference.scale_state(one, passes), state)
        ref = reference.snapshot_answers(
            reference.scale_state(one, sample_passes), k=c["top_k"], **kw)
        checks.update(reference.compare_challenge(ref, sample_got))
        one_pass = reference.snapshot_answers(one, k=c["top_k"], **kw)
        per_snap = [reference.compare_scalars(
            reference.scale_scalars(one_pass["scalars"], p), s)
            for p, s in got_scalars]
        checks["scalars_wrong"] += sum(per_snap)
        failed = sum(1 for x in per_snap if x)
        if any(checks.values()) and not failed:
            failed = 1
        checks = {k: (v, 0) for k, v in checks.items()}
    else:
        del engine
        worst = reference.sketch_checks(
            [s for _, s in snaps], reference.exact_counts(src, dst),
            passes=[p for p, _ in snaps], cfg=c)
        checks = {k: (v, reference.SKETCH_LIMITS[k]) for k, v in worst.items()}
        failed = int(any(v > lim for v, lim in checks.values()))
    e2e = {"setup_s": ctx.setup_s, INGEST[tier]: folded / wall,
           "snapshot_s": sum(walls) / len(walls)}
    window = {"wall_s": wall, "passes": len(walls), "packets": folded,
              "snapshot_walls": walls, "pass_walls": pass_walls}
    return DriverResult(e2e=e2e,
                        obs={"spans": spans, "trace": summary,
                             "window": window},
                        checks=checks, attempted=len(walls), failed=failed,
                        memory_peak_bytes=peak)
