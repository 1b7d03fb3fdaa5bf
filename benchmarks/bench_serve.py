"""Serve lane (DESIGN.md §2.7): what fault tolerance costs the service.

Three runs over the same plq capture quantify the recovery machinery:

  * ``baseline``      — the plain supervised loop (no checkpoints, no
    faults): steady-state packets/s, the throughput reference.
  * ``checkpointed``  — commit a watermarked checkpoint after every
    batch: the *durability tax* (per-commit wall + steady-state delta).
  * ``recovery``      — same, plus one injected crash mid-stream: restore
    wall, replay wall, and the end-to-end overhead of dying once.

The recovery run is also a correctness gate, mirroring
``bench_algorithms``/``bench_sketches``: its recovered snapshot must
answer every scalar query bit-identically to the baseline run
(``identical: true`` per row; hard AssertionError otherwise — CI parses
the JSON and fails on ``identical: false``).  Rows are written
machine-readably to ``BENCH_serve.json`` when a path is given.

    PYTHONPATH=src python -m benchmarks.bench_serve [--n N] [--json P]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from repro.challenge.pipeline import window_column
from repro.data.faults import FaultConfig
from repro.data.plq import read_plq
from repro.obs import Histogram
from repro.stream.engine import StreamConfig, steady_state
from repro.stream.recovery import run_service
from repro.stream.run import prepare_capture

# the lane measures per-batch durability overhead, not bulk throughput;
# 2^17 packets in 2^13-row groups = 16 commits per run (reported)
MAX_PACKETS = 1 << 17
N_WINDOWS = 8
IP_BINS = 1024


def _batch_latency(report) -> Dict[str, float]:
    """p50/p99 of the run's steady (compile-excluded) per-fold walls.

    Goes through the obs fixed-bucket histogram — the same estimator the
    serve CLI and CI telemetry smoke report — so the BENCH trajectory and
    the live metrics agree on what "p99 batch latency" means.
    """
    h = Histogram("serve_fold_seconds")
    for t in report.timings:
        if not t.compile:
            h.observe(t.total_s)
    return {"p50_s": h.quantile(0.5), "p99_s": h.quantile(0.99),
            "count": h.count}


def run(n: int = 1 << 17, json_path: Optional[str] = None) -> Dict[str, Dict]:
    n_eff = min(n, MAX_PACKETS)
    capped = f" (capped from n={n})" if n_eff < n else ""
    scale = max(n_eff.bit_length() - 1, 4)
    batch = min(1 << 13, n_eff)
    n_batches = -(-n_eff // batch)

    from .common import emit

    work = tempfile.mkdtemp(prefix="bench_serve_")
    path = prepare_capture(work, n_eff, scale, 0, batch)
    win_full = window_column(read_plq(path, ["ts"])["ts"], N_WINDOWS)
    cfg = StreamConfig(
        batch_capacity=batch, link_capacity=n_eff,
        n_windows=N_WINDOWS, ip_bins=IP_BINS, backend="auto",
    )

    def serve(tag: str, **kw) -> Dict:
        t0 = time.perf_counter()
        report = run_service(cfg, path, win_full, **kw)
        wall = time.perf_counter() - t0
        ss = steady_state(report.timings)
        return {"report": report, "wall_s": wall, "steady": ss,
                "latency": _batch_latency(report)}

    rows: Dict[str, Dict] = {}

    # ---- baseline: no durability machinery ----
    base = serve("baseline")
    base_scalars = {
        k: int(v)
        for k, v in base["report"].snapshot().results.scalars.as_dict().items()
    }
    emit("serve/baseline", base["steady"]["batch_s"],
         f"{base['steady']['packets_per_s']:,.0f} packets/s steady, "
         f"{n_batches} batches of {batch} n={n_eff}{capped}")
    rows["baseline"] = {
        "wall_s": base["wall_s"],
        "steady_packets_per_s": base["steady"]["packets_per_s"],
        "steady_batch_s": base["steady"]["batch_s"],
        "n_batches": n_batches,
        "batch_latency": base["latency"],
    }

    # ---- checkpointed: the durability tax ----
    ck = serve("checkpointed", checkpoint_dir=os.path.join(work, "ck"))
    walls = ck["report"].checkpoint_walls
    ck_mean = float(np.mean(walls)) if walls else 0.0
    emit("serve/checkpoint_commit", ck_mean,
         f"{len(walls)} watermarked commits, total "
         f"{sum(walls):.3f}s over {ck['wall_s']:.3f}s run")
    rows["checkpointed"] = {
        "wall_s": ck["wall_s"],
        "steady_packets_per_s": ck["steady"]["packets_per_s"],
        "batch_latency": ck["latency"],
        "commits": len(walls),
        "commit_wall_mean_s": ck_mean,
        "commit_wall_total_s": float(sum(walls)),
        # the durability tax: commits happen between folds, so express the
        # per-commit wall against one steady-state fold (compile excluded)
        "commit_tax_vs_fold":
            ck_mean / base["steady"]["batch_s"]
            if base["steady"]["batch_s"] else 0.0,
    }

    # ---- recovery: one crash mid-stream, gated on exactness ----
    rec = serve(
        "recovery",
        checkpoint_dir=os.path.join(work, "ck_crash"),
        faults=FaultConfig(crash_at_batch=n_batches // 2),
    )
    rep = rec["report"]
    assert rep.restarts == 1, "the armed crash must have fired exactly once"
    rec_scalars = {
        k: int(v)
        for k, v in rep.snapshot().results.scalars.as_dict().items()
    }
    identical = rec_scalars == base_scalars
    restore = float(sum(rep.restore_walls))
    emit("serve/recovery_restore", restore,
         f"replay {rep.health.batches_replayed} batches "
         f"({rep.replay_wall_s:.4f}s), snapshot "
         f"{'bit-identical' if identical else 'DIVERGED'}")
    rows["recovery"] = {
        "wall_s": rec["wall_s"],
        "batch_latency": rec["latency"],
        "restarts": rep.restarts,
        "restore_wall_s": restore,
        "replay_wall_s": rep.replay_wall_s,
        "replayed_batches": rep.health.batches_replayed,
        "crash_at_batch": n_batches // 2,
        "recovery_overhead_s": restore + rep.replay_wall_s,
        "identical": bool(identical),
        "health": rep.health.as_dict(),
    }

    # ---- roofline of the fold program itself: lower update_state at this
    # config's static shapes, charge it the baseline's steady update wall ----
    import jax
    import jax.numpy as jnp

    from repro.launch.roofline import program_roofline
    from repro.stream.engine import update_state
    from repro.stream.state import init_state

    from .common import device_kind

    state0 = init_state(cfg.link_capacity, cfg.ips, cfg.n_windows, cfg.ip_bins)
    z = jnp.zeros((batch,), jnp.int32)
    fold_fn = jax.jit(lambda s, a, b, c, nv: update_state(s, a, b, c, nv))
    fold_hlo = fold_fn.lower(
        state0, z, z, z, jnp.asarray(batch, jnp.int32)).compile().as_text()
    roofline = {
        "fold": program_roofline(fold_hlo, base["steady"]["update_s"],
                                 device_kind()),
    }
    emit("roofline/fold", roofline["fold"]["wall_s"],
         f"{roofline['fold']['roofline_fraction']:.4f} of peak "
         f"({roofline['fold']['bottleneck']}-bound)")
    emit("serve/batch_latency", base["latency"]["p99_s"],
         f"baseline p50={base['latency']['p50_s'] * 1e3:.2f}ms "
         f"p99={base['latency']['p99_s'] * 1e3:.2f}ms "
         f"over {base['latency']['count']} steady folds")

    if json_path:
        from .common import run_manifest

        with open(json_path, "w") as fh:
            json.dump({"n": n_eff, "scale": scale, "batch": batch,
                       "runs": rows, "roofline": roofline,
                       "manifest": run_manifest()}, fh, indent=2)
        print(f"serve/json,0,wrote {json_path}", flush=True)

    if not identical:
        diff = {k: (rec_scalars[k], v) for k, v in base_scalars.items()
                if rec_scalars[k] != v}
        raise AssertionError(
            f"recovered snapshot diverged from uninterrupted run: {diff}"
        )
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 17)
    ap.add_argument("--json", default="BENCH_serve.json")
    args = ap.parse_args()
    run(n=args.n, json_path=args.json or None)
