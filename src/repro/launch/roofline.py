"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch × shape × mesh) cell:

    compute term    = HLO_dot_FLOPs / (chips × 197 TFLOP/s bf16)
    memory term     = HLO_HBM_bytes / (chips × 819 GB/s)
    collective term = collective_bytes / (chips × 50 GB/s ICI)

All three numerators come from the loop-trip-exact HLO analysis
(launch/hloanalysis.py) of the compiled SPMD program — cost_analysis()
under-counts while bodies, see that module.  MODEL_FLOPS is the analytic
6·N·D (dense) / 6·N_active·D (MoE) for training, 2·N·D for serving; the
MODEL/HLO ratio flags remat/redundancy waste.

    python -m repro.launch.roofline --dir artifacts/dryrun [--mesh single]

:func:`program_roofline` is the *measured* counterpart used by the
benchmark lanes (DESIGN.md §2.8): given a timed compiled program's HLO
text and its steady-state wall, it reports achieved bytes/s and flops/s
against the :data:`DEVICE_PEAKS` ceiling of the device it ran on.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, Optional

# Peak rates per device for the *measured* roofline (program_roofline),
# keyed by ``jax.Device.device_kind``.  A kind missing here is an error,
# never a default: a fraction against another chip's peaks is wrong.
#   "TPU v5 lite" (TPU v5e): 197 TFLOP/s bf16 and 819 GB/s HBM per chip,
#     from Google Cloud's "TPU v5e" documentation.
#   "cpu": a commodity many-core node (~50 GB/s DRAM, ~0.5 TFLOP/s f32),
#     coarse on purpose — CPU fractions only track one machine over time.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9},
    "cpu": {"flops": 5e11, "bytes_per_s": 5e10},
}

PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"]["flops"]         # per chip
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["bytes_per_s"]       # per chip
LINK_BW = 50e9             # bytes/s per ICI link


def hardware_fingerprint(backend: Optional[str] = None) -> Dict[str, object]:
    """Coarse identity of the machine a measurement was taken on.

    Embedded in benchmark manifests, autotune tables and perf baselines so
    regression gates can tell "same box, got slower" (fail) apart from
    "different box, numbers incomparable" (skip cleanly).  ``cpu_model``
    comes from ``/proc/cpuinfo`` where available — CI runners and dev
    containers reliably differ there even when arch and core count match.
    """
    import os as _os
    import platform

    if backend is None:
        import jax

        backend = jax.default_backend()
    cpu_model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    if not cpu_model:
        cpu_model = platform.processor() or ""
    return {
        "backend": backend,
        "machine": platform.machine(),
        "cpu_count": _os.cpu_count() or 0,
        "cpu_model": cpu_model,
    }


def peak_table(device_kind: str) -> Dict[str, float]:
    """The peak row for ``device_kind``; raises ``KeyError`` for a kind
    that is not in :data:`DEVICE_PEAKS`."""
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no peak rates for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}"
        )
    return dict(DEVICE_PEAKS[device_kind], device_kind=device_kind)


def program_roofline(
    compiled_text: str, wall_s: float, device_kind: str
) -> Dict[str, object]:
    """Achieved-vs-peak roofline of one timed compiled program.

    ``compiled_text`` is the post-optimization HLO
    (``jit(f).lower(*args).compile().as_text()``) and ``wall_s`` the
    measured steady-state wall seconds per call of that same program.  The
    numerators come from the loop-trip-exact HLO traffic model
    (:func:`repro.launch.hloanalysis.analyze_hlo`); dividing by the wall
    gives achieved bytes/s and flops/s, and dividing those by the
    :data:`DEVICE_PEAKS` row of ``device_kind`` (the device the wall was
    measured on) gives the two roofline fractions.  The
    reported ``roofline_fraction`` is the max of the two — how close the
    program runs to the binding ceiling — and ``bottleneck`` names which
    ceiling binds (the challenge kernels are memory-bound: sort/scatter
    traffic, almost no dot math, exactly the GraphBLAST profile).

    Fractions can exceed 1.0: the traffic model charges every operand as
    an HBM round-trip, so a working set that actually lives in cache (CPU
    quick shapes especially) "achieves" more modeled bytes/s than DRAM
    peak.  That does not hurt the number's job — regression tracking at
    fixed shape/backend (ROADMAP item 5), where only the PR-over-PR delta
    matters.
    """
    from .hloanalysis import analyze_hlo

    peaks = peak_table(device_kind)
    a = analyze_hlo(compiled_text)
    hbm = float(a["hbm_bytes"])
    flops = float(a["dot_flops"])
    b_s = hbm / wall_s if wall_s > 0 else 0.0
    f_s = flops / wall_s if wall_s > 0 else 0.0
    frac_bw = b_s / peaks["bytes_per_s"]
    frac_fl = f_s / peaks["flops"]
    return {
        "device_kind": peaks["device_kind"],
        "wall_s": wall_s,
        "hbm_bytes": hbm,
        "dot_flops": flops,
        "peak_bytes_per_s": peaks["bytes_per_s"],
        "peak_flops_per_s": peaks["flops"],
        "achieved_bytes_per_s": b_s,
        "achieved_flops_per_s": f_s,
        "frac_peak_bw": frac_bw,
        "frac_peak_flops": frac_fl,
        "roofline_fraction": max(frac_bw, frac_fl),
        "bottleneck": "memory" if frac_bw >= frac_fl else "compute",
        "peak_buffer_bytes": float(a["peak_buffer_bytes"]),
    }

_LM_TOKENS = {"train_4k": 256 * 4096, "prefill_32k": 32 * 32768,
              "decode_32k": 128, "long_500k": 1}


def model_flops(arch: str, shape: str, kind: str) -> Optional[float]:
    """Analytic useful FLOPs per step (6·N·D train / 2·N·D serve)."""
    from ..configs import get_spec

    if arch in ("qwen2-72b", "minicpm-2b", "granite-8b", "arctic-480b",
                "mixtral-8x7b"):
        import importlib

        mod = importlib.import_module(
            f"repro.configs.{arch.replace('-', '_')}")
        cfg = mod.full_config()
        n = cfg.n_active_params
        d = _LM_TOKENS[shape]
        return (6.0 if kind == "train" else 2.0) * n * d

    if arch == "xdeepfm":
        from ..configs.xdeepfm import CFG, SHAPES

        info = SHAPES[shape]
        b = info["batch"]
        m, D = CFG.n_sparse, CFG.embed_dim
        cin = sum(2 * h * m * m * D + 2 * h * m * D for h in CFG.cin_layers)
        dims = [m * D, *CFG.mlp_dims, 1]
        mlp = sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        per = cin + mlp
        if shape == "retrieval_cand":
            return 2.0 * b * info["n_cand"] * D
        return (3.0 if info["kind"] == "train" else 1.0) * b * per

    if arch in ("schnet", "pna", "egnn", "graphsage-reddit"):
        from ..configs.common_gnn import GNN_SHAPES

        info = GNN_SHAPES[shape]
        N, E, F = info["n_nodes"], info["n_edges"], info["d_feat"]
        if arch == "graphsage-reddit":
            d = 128
            fwd = 2 * N * (2 * F * d + 2 * d * d + d * info["n_classes"])
        elif arch == "pna":
            d = 75
            fwd = 4 * (2 * E * 2 * d * d + 2 * N * 13 * d * d) + 2 * N * F * d
        elif arch == "schnet":
            d, rbf = 64, 300
            fwd = 3 * (2 * E * (rbf * d + d * d) + 2 * N * 3 * d * d)
        else:  # egnn
            d = 64
            fwd = 4 * (2 * E * ((2 * d + 1) * d + 2 * d * d)
                       + 2 * N * 3 * d * d) + 2 * N * F * d
        return 3.0 * fwd  # fwd + bwd ≈ 3× fwd

    return None  # network-sensing: sort/collective-bound, no dot math


def fix_hint(row: dict) -> str:
    dom, fam, kind = row["bottleneck"], row["arch"], row["kind"]
    if dom == "collective":
        if "moe" in row.get("note", "") or fam in ("mixtral-8x7b", "arctic-480b"):
            return "localize MoE dispatch per dp-shard (avoid sharded-axis sort)"
        return "re-shard so the gather/reduce stays shard-local; overlap with compute"
    if dom == "memory":
        if kind == "decode":
            return "KV cache is the stream: quantize cache to int8 / shrink replication"
        return "raise arithmetic intensity: larger per-chip batch, fuse, bf16 opt state"
    return "compute-bound — already at the right end of the roofline; check MODEL/HLO ratio for remat waste"


def build_rows(dirpath: str, mesh: Optional[str] = None, reanalyze: bool = True):
    rows = []
    for path in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if r.get("status") != "ok":
            if r.get("status") == "skipped":
                rows.append({"arch": r["arch"], "shape": r["shape"],
                             "mesh": r["mesh"], "status": "skipped"})
            continue
        if mesh and r["mesh"] != mesh:
            continue
        hlo_gz = path[:-5] + ".hlo.gz"
        if reanalyze and os.path.exists(hlo_gz):
            # apply the latest hloanalysis model without recompiling
            import gzip

            from .hloanalysis import analyze_hlo

            deep = analyze_hlo(gzip.open(hlo_gz, "rt").read())
            r.update({k: deep[k] for k in
                      ("collectives", "collective_bytes_total",
                       "dot_flops", "hbm_bytes")})
        chips = r["n_devices"]
        t_c = r.get("dot_flops", 0) / PEAK_FLOPS
        t_m = r.get("hbm_bytes", 0) / HBM_BW
        t_x = r.get("collective_bytes_total", 0) / LINK_BW
        dom = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
                  key=lambda kv: kv[1])[0]
        mf = model_flops(r["arch"], r["shape"], r["kind"])
        hlo_global = r.get("dot_flops", 0) * chips
        row = {
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "kind": r["kind"], "status": "ok", "chips": chips,
            "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
            "bottleneck": dom,
            "model_flops": mf, "hlo_flops_global": hlo_global,
            "useful_ratio": (mf / hlo_global) if (mf and hlo_global) else None,
            "bytes_per_device": r["memory_analysis"].get("argument_size_in_bytes", 0)
            + r["memory_analysis"].get("temp_size_in_bytes", 0),
            "hbm_ok": (r["memory_analysis"].get("argument_size_in_bytes", 0)
                       + r["memory_analysis"].get("temp_size_in_bytes", 0)) < 16e9,
            "note": r.get("note", ""),
        }
        row["hint"] = fix_hint(row)
        rows.append(row)
    return rows


def fmt_s(x) -> str:
    if x is None:
        return "-"
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.1f}ms"
    return f"{x * 1e6:.0f}us"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="artifacts/dryrun")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--csv", default="artifacts/roofline.csv")
    args = ap.parse_args()

    rows = build_rows(args.dir, args.mesh)
    ok = [r for r in rows if r["status"] == "ok"]
    hdr = ("| arch | shape | mesh | t_comp | t_mem | t_coll | bottleneck | "
           "MODEL/HLO | fits 16G | fix hint |")
    print(hdr)
    print("|" + "---|" * 10)
    for r in ok:
        ratio = f"{r['useful_ratio']:.2f}" if r["useful_ratio"] else "-"
        print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
              f"{fmt_s(r['t_compute_s'])} | {fmt_s(r['t_memory_s'])} | "
              f"{fmt_s(r['t_collective_s'])} | {r['bottleneck']} | {ratio} | "
              f"{'y' if r['hbm_ok'] else 'NO'} | {r['hint'][:60]} |")
    skipped = [r for r in rows if r["status"] == "skipped"]
    for r in skipped:
        print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | - | - | - | "
              f"skipped (inapplicable) | - | - | - |")

    if args.csv:
        os.makedirs(os.path.dirname(args.csv), exist_ok=True)
        import csv

        keys = ["arch", "shape", "mesh", "kind", "status", "chips",
                "t_compute_s", "t_memory_s", "t_collective_s", "bottleneck",
                "model_flops", "hlo_flops_global", "useful_ratio",
                "bytes_per_device", "hbm_ok", "hint"]
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys, extrasaction="ignore")
            w.writeheader()
            for r in rows:
                w.writerow(r)
        print(f"\nwrote {args.csv} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
