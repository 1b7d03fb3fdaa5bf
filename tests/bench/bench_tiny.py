"""Shared helpers of the benchmark's CPU tests: a tiny copy of ``bench/``.

``tiny_root(tmp)`` copies the benchmark's directory into ``tmp`` and shrinks
every traffic mix and configuration to a size the CPU runs in seconds, so a
test can drive the rest of a run (:func:`run_tiny`) without the chip.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

CELLS = ("batch-rmat22", "service-exact-rmat20", "service-sketch-rmat20")


def _rewrite(path: Path, **changes) -> None:
    d = json.loads(path.read_text())
    d.update({k: v for k, v in changes.items() if k in d})
    path.write_text(json.dumps(d))


def tiny_root(tmp, n_packets: int = 4096, scale: int = 12) -> Path:
    """A copy of ``bench/`` under ``tmp`` with tiny traffic and configs."""
    root = Path(tmp) / "bench"
    shutil.copytree(ROOT / "bench", root,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    for path in (root / "traffic").glob("*.json"):
        _rewrite(path, n_packets=n_packets, scale=scale,
                 row_group_size=n_packets // 4)
    for path in (root / "configs").glob("*.json"):
        _rewrite(path, table_capacity=n_packets,
                 batch_capacity=n_packets // 4,
                 link_capacity=2 * n_packets, ip_capacity=4 * n_packets)
    return root


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def run_tiny(root: Path, cell: str, *, seed: int = 2**31 + 17,
             seconds: float = 0.5, trace: bool = False) -> dict:
    """One run of ``cell`` from ``root`` on the CPU, past the chip check."""
    import jax

    from bench import harness

    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(),
                            devices=jax.devices(), benchmark=benchmark(),
                            root=root)


def run_driver(root: Path, cell: str, *, seed: int = 2**31 + 17,
               seconds: float = 0.5, trace: bool = False):
    """The window driver's own result of one tiny run (window accounting
    included)."""
    from bench import harness

    return harness.run_driver(cell, seed, seconds, trace,
                              t_start=time.perf_counter(), root=root)
