#!/usr/bin/env python3
"""The controls of ``correct``: the reference one precision below the
configuration, put in the program's place and judged by the same comparison.

    python bench/control.py --workload <cell> --seeds 1 2 3 --passes P

For each seed it draws the cell's capture, computes the reference and each
of the cell's controls over ``P`` passes of it, and prints each compared
number beside its limit and whether the control came out correct (it must
not).  The benchmark's own runs never run this; it needs no accelerator.

- exact cells (``hashed_links``): link keys packed into 32 bits (a hash of
  the pair) in place of two 32-bit ids, so colliding links merge;
- sketch cell: ``int16``, every counter held in int16 in place of int32;
  ``cms_depth1``, the maxima from a Count-Min of one row in place of four.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CONTROLS = {"batch": ("hashed_links",), "exact": ("hashed_links",),
            "sketch": ("int16", "cms_depth1")}


def _kind(work: dict, config: dict) -> str:
    return "batch" if work["driver"] == "batch" else config["tier"]


def controls_of(cell: str, root=None) -> tuple:
    from bench import harness

    root = harness.BENCH_DIR if root is None else root
    work = harness.load_json("workloads", cell, root)
    return CONTROLS[_kind(work, harness.load_json("configs", work["config"],
                                                  root))]


def control_checks(cell: str, seed: int, passes: int, root=None,
                   control: str = None) -> dict:
    """``{name: (value, limit)}`` of one control of ``cell`` at ``seed``
    (the cell's first control where ``control`` is None)."""
    import numpy as np

    from bench import harness, reference
    from bench.traffic import generate

    root = harness.BENCH_DIR if root is None else root
    work = harness.load_json("workloads", cell, root)
    c = harness.load_json("configs", work["config"], root)
    t = harness.load_json("traffic", work["traffic"], root)
    kind = _kind(work, c)
    control = CONTROLS[kind][0] if control is None else control
    if control not in CONTROLS[kind]:
        raise KeyError(f"cell {cell!r} has no control {control!r}; "
                       f"it has {CONTROLS[kind]}")
    cols = generate(t, seed)
    src = cols["src"].astype(np.int64)
    dst = cols["dst"].astype(np.int64)
    win = reference.window_ids(cols["ts"], c["n_windows"])
    kw = dict(n_windows=c["n_windows"], ip_bins=c["ip_bins"])
    if kind == "batch":
        # the reference's own anonymization: each IP's rank
        ips, inv = np.unique(np.concatenate([src, dst]), return_inverse=True)
        a_src, a_dst = inv[:len(src)], inv[len(src):]
        ones = np.ones(len(src), np.int64)
        ref = reference.challenge_answers(a_src, a_dst, win, ones,
                                          k=c["top_k"], **kw)
        got = reference.challenge_control(a_src, a_dst, win, ones,
                                          k=c["top_k"], **kw)
        checks = {"anonymize_wrong": reference.anonymize_wrong(
            src, dst, a_src, a_dst), **reference.compare_challenge(ref, got)}
        return {k: (v, 0) for k, v in checks.items()}
    if kind == "exact":
        bpp = -(-t["n_packets"] // t["row_group_size"])
        one = reference.stream_state(src, dst, win, batches_per_pass=bpp, **kw)
        ref = reference.scale_state(one, passes)
        got = reference.stream_state_control(src, dst, win, passes,
                                             batches_per_pass=bpp, **kw)
        checks = reference.compare_stream_state(ref, got)
        checks.update(reference.compare_challenge(
            reference.snapshot_answers(ref, k=c["top_k"], **kw),
            reference.snapshot_answers(got, k=c["top_k"], **kw)))
        return {k: (v, 0) for k, v in checks.items()}
    exact = reference.exact_counts(src, dst)
    if control == "int16":
        got = reference.sketch_control(exact, passes, c["top_k"])
    else:
        got = reference.shallow_cms_control(
            exact, passes, c["top_k"], heavy=c["heavy_capacity"],
            width=c["cms_width"], depth=1)
    worst = reference.sketch_checks([got], exact, passes=[passes], cfg=c)
    return {k: (v, reference.SKETCH_LIMITS[k]) for k, v in worst.items()}


def _print(cell, seed, control, checks, **extra) -> None:
    correct = all(v <= lim for v, lim in checks.values())
    print(json.dumps({"workload": cell, "seed": seed, "control": control,
                      **extra, "correct": correct,
                      "checks": {k: {"value": v, "limit": lim}
                                 for k, (v, lim) in checks.items()}}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--passes", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for seed in args.seeds:
        for control in controls_of(args.workload):
            _print(args.workload, seed, control, control_checks(
                args.workload, seed, args.passes, control=control),
                passes=args.passes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
