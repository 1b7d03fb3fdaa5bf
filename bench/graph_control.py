#!/usr/bin/env python3
"""The controls of the Graphalytics cell's ``correct``: its reference one
precision below the configuration, put in the program's place and judged
by the same comparison.

    python bench/graph_control.py --workload graphalytics-g500s18 --seeds 1 2 3

For each seed it draws the cell's capture, gives the IPs their ranks as
anonymized ids, and prints, for each control, every compared number beside
its limit and whether the control came out correct (it must not).  The
benchmark's own runs never run this; it needs no accelerator.

- ``labels_f16``: WCC labels carried in float16 in place of float32, which
  holds ids exactly only up to 2048;
- ``ranks_bf16``: PageRank's rank vectors held in bfloat16 in place of
  float32, run for the steps the float64 reference takes;
- ``bfs_undirected``: BFS that ignores the links' direction.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CONTROLS = ("labels_f16", "ranks_bf16", "bfs_undirected")


def control_checks(cell: str, seed: int, control: str, root=None) -> dict:
    """``{name: (value, limit)}`` of one control of ``cell`` at ``seed``."""
    import numpy as np

    from bench import graph_reference as gr
    from bench import harness
    from bench.traffic import generate

    if control not in CONTROLS:
        raise KeyError(f"no control {control!r}; controls: {CONTROLS}")
    root = harness.BENCH_DIR if root is None else root
    work = harness.load_json("workloads", cell, root)
    c = harness.load_json("configs", work["config"], root)
    t = harness.load_json("traffic", work["traffic"], root)
    pr = c["pagerank"]
    cols = generate(t, seed)
    ips, inv = np.unique(np.concatenate([cols["src"], cols["dst"]]),
                         return_inverse=True)
    n = len(ips)
    src, dst = inv[:len(cols["src"])], inv[len(cols["src"]):]
    u, v = gr.links(src, dst)
    source = int(src[0])  # the first packet's source
    _, steps = gr.pagerank(u, v, n, pr["max_iters"], damping=pr["damping"],
                           tol=pr["tol"])
    got = {"bfs_levels": gr.bfs_levels(u, v, n, source),
           "wcc_labels": gr.wcc_labels(u, v, n),
           "pagerank_ranks": gr.pagerank(u, v, n, steps,
                                         damping=pr["damping"])[0],
           "pagerank_steps": steps}
    if control == "labels_f16":
        with np.errstate(over="ignore"):  # ids past 65504 read +inf
            got["wcc_labels"] = gr.wcc_labels(u, v, n, carrier=np.float16)
    elif control == "ranks_bf16":
        got["pagerank_ranks"] = gr.pagerank(u, v, n, steps,
                                            damping=pr["damping"],
                                            carrier=gr.round_bf16)[0]
    else:
        got["bfs_levels"] = gr.bfs_levels(np.concatenate([u, v]),
                                          np.concatenate([v, u]), n, source)
    checks = gr.checks(got, u, v, n, source, damping=pr["damping"],
                       tol=pr["tol"], max_steps=pr["max_iters"])
    return {k: (val, gr.LIMITS[k]) for k, val in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for seed in args.seeds:
        for control in CONTROLS:
            checks = control_checks(args.workload, seed, control)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "control": control,
                "correct": all(v <= lim for v, lim in checks.values()),
                "checks": {k: {"value": v, "limit": lim}
                           for k, (v, lim) in checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
