"""Benchmark helpers: timing, CSV emission, shared synthetic inputs."""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.data.rmat import synthetic_packets
from repro.obs import SCHEMA_VERSION, run_context

__all__ = ["time_fn", "emit", "packet_arrays", "run_manifest",
           "kernel_roofline"]


def run_manifest() -> Dict:
    """The provenance stamp every ``BENCH_*.json`` carries (ISSUE: the
    trajectory must be diffable across PRs without out-of-band notes).

    Host-side by construction — the timestamp is taken here, outside any
    jit, and passed into the payload as data.
    """
    from repro.launch.roofline import hardware_fingerprint

    ctx = run_context()
    return {
        "schema_version": SCHEMA_VERSION,
        "git_sha": ctx["git_sha"],
        "backend": ctx["backend"],
        "device": str(jax.devices()[0]),
        "jax_version": ctx["jax_version"],
        "python": ctx["python"],
        "timestamp": time.time(),
        "fingerprint": hardware_fingerprint(),
    }


def kernel_roofline(fn: Callable, *args, iters: int = 5) -> Dict:
    """Compile ``fn`` once, time it steady-state, report achieved-vs-peak.

    One definition shared by every lane: ``jit(fn)`` is lowered/compiled
    for the given arguments, the *same* executable is timed with
    :func:`time_fn` (compile excluded — the warmup call hits the jit
    cache), and its post-optimization HLO + wall feed
    :func:`repro.launch.roofline.program_roofline`.
    """
    from repro.launch.roofline import program_roofline

    jitted = jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    wall = time_fn(jitted, *args, iters=iters)
    return program_roofline(compiled.as_text(), wall, device_kind())


def device_kind() -> str:
    """The measured device's kind — the key of its roofline peak row."""
    return jax.devices()[0].device_kind


def time_fn(fn: Callable, *args, iters: int = 5, warmup: int = 1) -> float:
    """Median wall seconds per call (jax results block_until_ready)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out) if _is_jax(out) else None
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        if _is_jax(out):
            jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _is_jax(x) -> bool:
    return any(isinstance(l, jax.Array) for l in jax.tree.leaves(x))


def emit(name: str, seconds: float, derived: str = "") -> None:
    """CSV row: name,us_per_call,derived."""
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


_CACHE: Dict = {}


def packet_arrays(n: int, scale: int = 18, seed: int = 0):
    key = (n, scale, seed)
    if key not in _CACHE:
        cols = synthetic_packets(n, scale=scale, seed=seed)
        _CACHE[key] = (cols["src"].astype(np.int32), cols["dst"].astype(np.int32))
    return _CACHE[key]
