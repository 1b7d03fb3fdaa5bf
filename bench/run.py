#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Generates the cell's capture from ``--seed``, warms every program the window
runs (through JAX's persistent compilation cache in ``bench/.cache/jax``),
measures for ``--seconds``, checks what the window produced against the
NumPy reference, and prints one JSON line as the last line of stdout:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
``breakdown`` (``--trace 1``) and ``checks``, each compared number beside its
limit.  Exits non-zero with no such line when JAX finds no TPU or fewer chips
than the cell asks for, or when the program under test is not beside the
benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".cache" / "jax"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    benchmark = harness.load_benchmark()
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if args.workload not in cells:
        print(f"bench: no cell {args.workload!r}; cells: {sorted(cells)}",
              file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program under test is not at {ROOT / 'src'}",
              file=sys.stderr)
        return 2

    # libtpu would otherwise log to the fixed /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    chips = cells[args.workload]["chips"]
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devices[0].platform!r}); the "
              "benchmark measures only on the chip", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: cell {args.workload!r} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    line = harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_start=T_START,
                            devices=devices[:chips], benchmark=benchmark)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
