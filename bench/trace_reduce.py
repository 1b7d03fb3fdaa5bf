"""Reduce a JAX profiler trace to device busy and idle time.

``reduce(profile, ...)`` takes a ``jax.profiler.ProfileData`` and returns:

- ``window_s``: the length of the host annotation that marks the traced
  window (``bench.window``);
- ``busy_s``: the union of the device's op intervals (the ``XLA Ops`` line
  of each ``/device:TPU:<n>`` plane) inside that window, averaged over
  chips;
- ``idle_share``: ``1 - busy_s / window_s``;
- ``device_ops``: the ten HLO ops (short names) with the most device time,
  summed over chips and divided by their number;
- ``idle_gaps``: the idle time between merged op intervals of the first
  device, cut where host spans begin or end and each piece named by the
  innermost host span that holds it (a ``bench.*`` annotation, or a span
  the caller maps onto the trace), the ten names with the most idle time.

Times are seconds.  A trace with no device plane gives ``busy_s == 0``.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
# one plane per chip; other /device: planes (host offload, non-core units)
# would halve an average over chips
CHIP_PLANE = re.compile(r"^/device:TPU:\d+$")
Interval = Tuple[float, float, str]


@contextlib.contextmanager
def profile(trace_dir: str):
    """Record a profiler trace of the body into ``trace_dir``."""
    import jax

    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> Iterable[Interval]:
    for e in line.events:
        start = float(e.start_ns)
        yield start, start + float(e.duration_ns), e.name


def op_name(event_name: str) -> str:
    """An HLO op's short name: ``%fusion.12`` of ``%fusion.12 = s32[..] ...``."""
    return event_name.split(" = ", 1)[0]


def device_ops(pd) -> List[List[Interval]]:
    """Per chip, its op intervals (ns)."""
    out = []
    for plane in pd.planes:
        if not CHIP_PLANE.match(plane.name):
            continue
        ops = [iv for line in plane.lines if line.name == OPS_LINE
               for iv in _events(line)]
        out.append(ops)
    return out


def host_spans(pd, prefix: str = "bench.") -> List[Interval]:
    """The benchmark's own annotations on the host threads (ns)."""
    return [iv for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for iv in _events(line)
            if iv[2].startswith(prefix)]


def merge(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, sorted."""
    merged: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(spans: Sequence[Interval], t: float) -> str:
    best: Optional[Interval] = None
    for sp in spans:
        if sp[0] <= t <= sp[1] and (best is None
                                    or sp[1] - sp[0] < best[1] - best[0]):
            best = sp
    return best[2] if best is not None else "outside any span"


def _top(pairs: dict, n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def reduce(pd, *, program_spans: Sequence[Tuple[float, float, str]] = (),
           clock0: Optional[float] = None) -> dict:
    """Busy, idle and breakdown of the window marked ``bench.window``.

    ``program_spans`` are ``(start_s, end_s, name)`` on the host's
    ``perf_counter`` clock; ``clock0`` is that clock read on entering the
    ``bench.window`` annotation, which pins them onto the trace's clock.
    """
    ann = host_spans(pd)
    windows = [sp for sp in ann if sp[2] == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    lo, hi, _ = windows[0]
    spans = [sp for sp in ann if sp[2] != WINDOW]
    if clock0 is not None:
        spans += [(lo + (s - clock0) * 1e9, lo + (e - clock0) * 1e9, name)
                  for s, e, name in program_spans]
    devices = device_ops(pd)
    window_s = (hi - lo) / 1e9
    busy = [merge(ops, lo, hi) for ops in devices]
    busy_s = (sum(e - s for b in busy for s, e in b) / 1e9 / len(devices)
              if devices else 0.0)
    per_op: dict = {}
    for ops in devices:
        for s, e, name in ops:
            d = (min(e, hi) - max(s, lo)) / 1e9
            if d > 0:
                name = op_name(name)
                per_op[name] = per_op.get(name, 0.0) + d / len(devices)
    idle: dict = {}
    for s, e in gaps(busy[0] if busy else [], lo, hi):
        # cut the gap where host spans begin or end inside it
        cuts = sorted({s, e} | {p for sp in spans for p in sp[:2] if s < p < e})
        for a, b in zip(cuts, cuts[1:]):
            name = _innermost(spans, (a + b) / 2)
            idle[name] = idle.get(name, 0.0) + (b - a) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "device_ops": _top(per_op),
        "idle_gaps": _top(idle),
    }


def reduce_dir(trace_dir: str, **kw) -> dict:
    return reduce(load(find_xplane(trace_dir)), **kw)
