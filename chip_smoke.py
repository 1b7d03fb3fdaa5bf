#!/usr/bin/env python3
"""Bring-up smoke: the challenge's main paths on one TPU, checked by oracle.

    python chip_smoke.py               # one chip: phases below
    python chip_smoke.py --scale 26    # batch challenge at 2^26 packets
    python chip_smoke.py --chips 4     # only the four-chip shard_map suite

One chip, in one process, through the entry points a user calls:

  batch    ``run_challenge`` at 2^scale RMAT packets (8 windows, 1024
           activity bins, top-10, ``fused=True`` so the one-program,
           buffer-donated path runs too), every scalar checked against the
           NumPy oracle; then the sketch tier over the same capture, every
           estimate checked against its configured error bound.
  kernels  ``analyze`` with ``backend="pallas"`` on the batch phase's
           anonymized table, bit-identical to its ``backend="auto"``
           result.  ``auto`` sends every reduction over more than 4096
           segments to XLA, so the 8 x 1024 per-window activity histogram
           runs as a compiled kernel only here.
  stream   ``python -m repro.stream.run`` at 2^22 packets in 4
           micro-batches, with its own NumPy-oracle check.

``--chips N`` (N > 1) runs only ``distributed_scalar_queries`` over all N
chips on a 2^26-packet RMAT table, compared with ``ref_run_all_queries``
and with the single-chip ``run_all_queries`` on device 0.

Earlier lines are diagnostics: per phase its walls, ``compile_s`` (first
call minus a warm call; ``precompile_s`` for the four-chip path, which
compiles both programs while the host generates the table), the device
kind and the device's peak bytes in use so far, all from one unrepeated
run.  The last line of stdout is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The script exits non-zero, with no such line, when JAX finds no TPU, when
fewer devices exist than ``--chips`` asks for, or on any mismatch.
Captures are generated from ``--seed`` into ``<repo>/.chip_smoke``,
which is removed at exit; the compile cache is the only other thing
written.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
STREAM_SCALE = 22
STREAM_BATCHES = 4
DIST_SCALE = 26


class SmokeFailure(Exception):
    """A phase produced a wrong answer."""


def _device_line(phase: str, **walls) -> None:
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    parts = " ".join(
        f"{k}={v:.6f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in walls.items()
    )
    print(f"[{phase}] one unrepeated run: {parts} device_kind={dev.device_kind!r} "
          f"peak_bytes_in_use={peak}", flush=True)


def _timed(fn, *args):
    """(result, first-call wall, warm-call wall) of a jitted ``fn``."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out2 = jax.block_until_ready(fn(*args))
    t2 = time.perf_counter()
    del out2
    return out, t1 - t0, t2 - t1


def phase_batch(scale: int, seed: int, workdir: str):
    from repro.challenge.pipeline import ChallengeConfig, run_challenge
    from repro.challenge.run import (format_sketch, run_sketch_tier,
                                     verify_scalars, verify_sketch)
    from repro.core.sketch import SketchConfig

    cfg = ChallengeConfig(scale=scale, n_windows=8, ip_bins=1024, top_k=10,
                          fused=True, backend="auto", seed=seed,
                          workdir=workdir)
    t0 = time.perf_counter()
    run = run_challenge(cfg)
    wall = time.perf_counter() - t0
    print(run.timings.format_table(), flush=True)
    t = run.timings
    _device_line("batch", packets=t.n_packets, phase_wall_s=wall,
                 read_s=t.read_s, build_s=t.build_s,
                 anonymize_s=t.anonymize_s, analyze_s=t.analyze_s,
                 fused_s=t.fused_s, compile_s=t.compile_s)
    bad = verify_scalars(run)
    if bad:
        raise SmokeFailure(f"batch challenge: {bad} scalar(s) disagree "
                           "with the NumPy oracle")
    print("[batch] all scalar queries match the NumPy oracle", flush=True)

    sk_cfg = SketchConfig(seed=seed)
    t0 = time.perf_counter()
    snap = run_sketch_tier(run.capture, sk_cfg, backend="auto", top_k=10)
    t1 = time.perf_counter()
    run_sketch_tier(run.capture, sk_cfg, backend="auto", top_k=10)
    t2 = time.perf_counter()
    print(format_sketch(snap), flush=True)
    _device_line("sketch", packets=t.n_packets, first_wall_s=t1 - t0,
                 warm_wall_s=t2 - t1, compile_s=(t1 - t0) - (t2 - t1))
    s = run.results.scalars
    bad = verify_sketch(snap, {f.name: getattr(s, f.name)
                               for f in dataclasses.fields(s)})
    if bad:
        raise SmokeFailure(f"sketch tier: {bad} estimate(s) outside their "
                           "configured bounds")
    print("[sketch] all estimates within their configured bounds", flush=True)
    return run


def phase_kernels(run, pallas: str = "pallas") -> None:
    import jax
    import numpy as np

    from repro.challenge.pipeline import analyze

    cfg = run.config
    fn = jax.jit(lambda t: analyze(t, n_windows=cfg.n_windows,
                                   ip_bins=cfg.ip_bins, k=cfg.top_k,
                                   backend=pallas))
    kern, first, warm = _timed(fn, run.anon_table)
    _device_line("kernels", packets=run.timings.n_packets, warm_s=warm,
                 compile_s=first - warm)
    paths, leaves_a = zip(*jax.tree_util.tree_flatten_with_path(run.results)[0])
    leaves_k, tree_k = jax.tree.flatten(kern)
    if tree_k != jax.tree.structure(run.results):
        raise SmokeFailure("kernels: result structures differ")
    diff = [jax.tree_util.keystr(p)
            for p, a, k in zip(paths, leaves_a, leaves_k)
            if not np.array_equal(np.asarray(a), np.asarray(k))]
    if diff:
        raise SmokeFailure(f"kernels: backend={pallas!r} differs from "
                           f"backend={cfg.backend!r} in {diff}")
    print(f"[kernels] backend={pallas!r} bit-identical to "
          f"backend={cfg.backend!r} over {len(leaves_k)} result arrays",
          flush=True)


def phase_stream(seed: int, workdir: str) -> None:
    from repro.stream.run import main as stream_main

    argv = ["--scale", str(STREAM_SCALE), "--batches", str(STREAM_BATCHES),
            "--seed", str(seed), "--workdir", workdir]
    print(f"[stream] python -m repro.stream.run {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    rc = stream_main(argv)
    wall = time.perf_counter() - t0
    _device_line("stream", packets=1 << STREAM_SCALE, phase_wall_s=wall,
                 compile_s="in batch 0 of the table above")
    if rc != 0:
        raise SmokeFailure(f"stream fold: repro.stream.run exited {rc}")
    print("[stream] all scalar queries match the NumPy oracle", flush=True)


def phase_distributed(scale: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.challenge.pipeline import (_distributed_suite,
                                          distributed_scalar_queries)
    from repro.core.queries import run_all_queries
    from repro.core.ref import ref_run_all_queries
    from repro.core.table import Table
    from repro.data.rmat import synthetic_packets

    n, n_dev = 1 << scale, len(jax.devices())
    # Compile both programs while the host generates the table, each in a
    # thread (XLA compiles without the interpreter lock); the persistent
    # compilation cache then hands them to the calls below.
    col, nv = (jax.ShapeDtypeStruct((n,), jnp.int32),
               jax.ShapeDtypeStruct((), jnp.int32))
    shapes = Table(columns={"src": col, "dst": col}, n_valid=nv)
    compiles = [
        threading.Thread(target=lambda: jax.jit(run_all_queries)
                         .lower(shapes).compile()),
        threading.Thread(target=lambda: _distributed_suite(n_dev)
                         .lower(col, col, col, nv).compile()),
    ]
    t_compile = time.perf_counter()
    for th in compiles:
        th.start()
    t0 = time.perf_counter()
    cols = synthetic_packets(n, scale=scale, seed=seed)
    src = cols["src"].astype(np.int32)
    dst = cols["dst"].astype(np.int32)
    print(f"[distributed] generated {len(src):,} RMAT packets in "
          f"{time.perf_counter() - t0:.3f}s", flush=True)

    # the oracle's sorts release the GIL: let it run beside the device work
    ref: dict = {}
    oracle = threading.Thread(target=lambda: ref.update(ref_run_all_queries(
        src.astype(np.int64), dst.astype(np.int64))))
    oracle.start()
    for th in compiles:
        th.join()
    precompile_s = time.perf_counter() - t_compile
    # uncommitted arrays: device 0 for the single-chip run, resharded onto
    # the mesh by the shard_map program
    table = Table.from_dict({"src": src, "dst": dst}, n_valid=len(src))
    single, s_first, s_warm = _timed(jax.jit(run_all_queries), table)
    dist, d_first, d_warm = _timed(distributed_scalar_queries, table)
    t0 = time.perf_counter()
    oracle.join()
    _device_line("distributed", packets=len(src), chips=n_dev,
                 precompile_s=precompile_s, single_chip_first_s=s_first,
                 single_chip_warm_s=s_warm, sharded_first_s=d_first,
                 sharded_warm_s=d_warm,
                 oracle_wait_s=time.perf_counter() - t0)
    wrong = []
    for k, v in ref.items():
        got_s, got_d = int(getattr(single, k)), int(getattr(dist, k))
        print(f"  {k:24s} oracle={v:>12,} single={got_s:>12,} "
              f"{n_dev}-chip={got_d:>12,}", flush=True)
        if got_s != v or got_d != v:
            wrong.append(k)
    if wrong:
        raise SmokeFailure(f"distributed: {wrong} disagree with the oracle")
    print(f"[distributed] {n_dev}-chip shard_map suite == single chip == "
          "NumPy oracle", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=24, choices=range(10, 27),
                    metavar="{10..26}",
                    help="batch challenge at 2^scale packets (default 24)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1,
                    help="1: the one-chip phases; N > 1: only the N-chip "
                         "shard_map suite")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); this "
              "smoke has no CPU fallback", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} asks for more devices than "
              f"the {len(devices)} JAX found", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind!r}, "
          f"jax {jax.__version__}, compile cache {use_compile_cache()}",
          flush=True)

    workdir = ROOT / ".chip_smoke"  # captures; removed at exit
    workdir.mkdir(exist_ok=True)
    try:
        if args.chips > 1:
            phase_distributed(DIST_SCALE, args.seed)
        else:
            run = phase_batch(args.scale, args.seed, str(workdir))
            phase_kernels(run)
            del run
            phase_stream(args.seed, str(workdir))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
