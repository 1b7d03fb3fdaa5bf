"""Find a cell's files by name, run its driver, and assemble the result line.

``run_cell`` is one run of one cell.  ``BENCHMARK.json`` says which
end-to-end and per-layer metrics the cell reports; ``workloads/<cell>.json``
names its configuration, traffic mix and driver, each found by name under
the benchmark's directory.  A driver returns a :class:`DriverResult`; the
harness keeps the end-to-end metrics the cell reports (``--trace 0``) or
asks each per-layer metric's reader for its value (``--trace 1``), and
decides ``correct`` from the window driver's comparisons against their
limits.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def load_json(kind: str, name: str, root: Path = BENCH_DIR) -> dict:
    """``<root>/<kind>/<name>.json``: a workload, configuration or traffic."""
    path = Path(root) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {name!r} at {path}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str, root: Path = BENCH_DIR):
    """``<root>/<kind>/<name>.py`` as a module: a driver or a metric reader."""
    path = Path(root) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(benchmark: dict, cell: str) -> Tuple[List[dict], List[dict]]:
    """The end-to-end and the per-layer metrics that ``cell`` reports."""
    return ([m for m in benchmark["end_to_end"] if _listed(m, cell)],
            [m for m in benchmark["per_layer"] if _listed(m, cell)])


class CompileCounter:
    """JAX's compile requests and persistent-cache misses, from its
    monitoring events.  A request that hits the cache loads a program; a
    miss compiles one."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.requests = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.misses += name == self.MISS

    def _duration(self, name, _secs, **_):
        self.requests += name == self.REQUEST

    def read(self) -> Tuple[int, int]:
        return self.requests, self.misses

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's files, the run's arguments, and where
    to write.  ``step`` times each part of set-up since the previous one;
    ``mark_setup`` ends set-up at the first timed operation and
    ``end_window`` closes the window, each reading the compile counter."""

    cell: str
    seed: int
    seconds: float
    trace: bool
    config: dict
    traffic: dict
    workdir: Path
    trace_dir: Path
    t_start: float
    setup_s: Optional[float] = None
    steps: Dict[str, float] = dataclasses.field(default_factory=dict)
    compiles: Optional[CompileCounter] = None
    window_compiles: Tuple[int, int] = (0, 0)

    def step(self, name: str) -> None:
        now = time.perf_counter()
        self.steps[name] = now - self.t_start - sum(self.steps.values())

    def mark_setup(self) -> None:
        self.step("last")
        self.setup_s = time.perf_counter() - self.t_start
        if self.compiles is not None:
            self.window_compiles = self.compiles.read()

    def end_window(self) -> None:
        """Keep the compile requests and misses made inside the window."""
        if self.compiles is not None:
            self.window_compiles = tuple(
                b - a for a, b in zip(self.window_compiles,
                                      self.compiles.read()))


@dataclasses.dataclass
class DriverResult:
    """One run's numbers.  ``e2e`` holds every end-to-end metric the driver
    measured; ``obs`` what the per-layer readers read (``spans``: name ->
    durations, ``trace``: the trace reduction or None, ``window``: the
    window's wall, passes and packets); ``checks`` maps a
    compared number's name to ``(value, limit)``."""

    e2e: Dict[str, float]
    obs: dict
    checks: Dict[str, Tuple[float, float]]
    attempted: int
    failed: int
    memory_peak_bytes: int


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def run_driver(cell: str, seed: int, seconds: float, trace: bool, *,
               t_start: float, root: Path = BENCH_DIR
               ) -> Tuple[Context, DriverResult]:
    """Load ``cell``'s files by name and run its window driver once."""
    work = load_json("workloads", cell, root)
    scratch = Path(root) / ".cache" / "run" / f"{cell}-{seed}"
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  config=load_json("configs", work["config"], root),
                  traffic=load_json("traffic", work["traffic"], root),
                  workdir=scratch / "capture", trace_dir=scratch / "trace",
                  t_start=t_start, compiles=CompileCounter())
    ctx.workdir.mkdir(parents=True, exist_ok=True)
    try:
        return ctx, load_module("drivers", work["driver"], root).run(ctx)
    finally:
        ctx.compiles.close()
        shutil.rmtree(scratch, ignore_errors=True)


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices, benchmark: Optional[dict] = None,
             root: Path = BENCH_DIR) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    benchmark = load_benchmark() if benchmark is None else benchmark
    e2e_metrics, layer_metrics = cell_metrics(benchmark, cell)
    ctx, res = run_driver(cell, seed, seconds, trace, t_start=t_start,
                          root=root)

    metrics = {}
    if not trace:
        for m in e2e_metrics:
            if m["name"] not in res.e2e:
                raise KeyError(f"the driver of cell {cell!r} measured no "
                               f"{m['name']!r}")
            metrics[m["name"]] = {"value": res.e2e[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in layer_metrics:
            value = load_module("metrics", m["name"], root).read(res.obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    line = {"correct": all(v <= lim for v, lim in res.checks.values()),
            "attempted": res.attempted, "failed": res.failed,
            "metrics": metrics, "device": device}
    summary = res.obs.get("trace")
    if trace and summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in res.checks.items()}
    for name, sec in ctx.steps.items():
        print(f"setup {name} {sec:.6f} s", file=sys.stderr)
    window = res.obs.get("window", {})
    requests, misses = ctx.window_compiles
    print(f"window compile_requests={requests} cache_misses={misses} "
          + " ".join(f"{k}={window[k]}" for k in
                     ("wall_s", "passes", "pass_walls") if k in window),
          file=sys.stderr)
    return line


def print_result(line: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result as
    the last line of stdout."""
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
