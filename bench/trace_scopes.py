"""Name a trace's device time and idle time by the program's own marks.

An addition to :mod:`bench.trace_reduce` for a program that marks itself:
``jax.named_scope`` names in each device op's ``op_name`` metadata, and
``repro.<span path>`` profiler annotations around its ``repro.obs`` spans.
``reduce(path)`` returns :func:`bench.trace_reduce.reduce`'s summary of the
``bench.window`` annotation, plus:

- ``device_scopes``: ``[scope path, seconds]``, every path with the union of
  the intervals of the ops under it (its own and its descendants'), so a
  ``while`` op and the body ops inside it count once; averaged over chips,
  longest first;
- ``scoped_share``: the share of busy time under any scope;
- ``scoped_ops``: the ten ops with the most device time, each as
  ``[short name, scope path, seconds]``;
- ``idle_spans``: idle time named by the innermost ``bench.*`` or
  ``repro.*`` annotation that holds it (``outside any span`` elsewhere),
  the ten names with the most idle time, and ``idle_program_share``, the
  share of idle time under a ``repro.*`` annotation.

``ProfileData`` names an op event by its HLO text, which two programs may
share, and does not carry the event's metadata stats, so the op events and
each one's ``tf_op`` stat (the op's ``op_name``, e.g.
``jit(f)/analyze/windowed/while:``) are read from the ``.xplane.pb`` file's
protobuf wire format directly.  A scope path
is the scopes of that name up to its first nested call (a ``jit(...)``
part after the program's own, or a ``closed_call``): below it JAX repeats
the names that were open where the callee was first traced, which may be
another scope's.  The parts JAX adds for control flow (``while``,
``body``, ``cond``, ``branch_<n>_fun``) and the last part, the primitive,
are dropped.  An op without the stat, as a ``while`` op on a TPU is, takes
the scope path that all the scoped ops running inside it share; an op with
nothing left has the scope path ``""``.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterator, List, Sequence, Tuple

from bench import trace_reduce

PREFIXES = ("bench.", "repro.")
PROGRAM = "repro."
UNSCOPED = ""
# parts of an op_name that JAX's control flow adds, and parts that open a
# nested call
_CONTROL = frozenset({"while", "body", "cond", "scan"})
_CALLS = frozenset({"closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call", "checkpoint", "remat", "pjit",
                    "shard_map"})
_BRANCH = re.compile(r"^branch_\d+_fun$")
_TF_OP = "tf_op"


def scope_of(op_name: str) -> str:
    """The scope path of one op's ``op_name`` (``""`` when it has none)."""
    if op_name.endswith(":") or ":" in op_name.rsplit("/", 1)[-1]:
        op_name = op_name.rsplit(":", 1)[0]   # the stat's "name:type" form
    out: List[str] = []
    for i, part in enumerate(op_name.split("/")[:-1]):
        if i == 0 and "(" in part:
            continue                          # the program's own jit(...)
        if "(" in part or part in _CALLS:
            break
        if part and part not in _CONTROL and not _BRANCH.match(part):
            out.append(part)
    return "/".join(out)


# -- the .xplane.pb wire format (XSpace > XPlane > event and stat metadata) --

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: ints for varints, bytes
    for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_entry(b: bytes) -> Tuple[int, bytes]:
    entry = dict(_fields(b))
    return entry.get(1, 0), entry.get(2, b"")


def chip_ops(xplane: bytes
             ) -> List[Tuple[str, List[trace_reduce.Interval],
                             Dict[int, Tuple[str, str]]]]:
    """Per chip plane: its name, the ``XLA Ops`` events as ``(start_ns,
    end_ns, metadata id)``, and each metadata id's ``(HLO text, op_name)``.

    Two programs may hold ops of the same HLO text, each with its own
    metadata, so an op is known by its event's metadata id, not its name.
    """
    out = []
    for field, plane in _fields(xplane):
        if field != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 3:
                lines.append(v)
            elif f == 4:
                metas.append(_map_entry(v))
            elif f == 5:
                sid, meta = _map_entry(v)
                stat_names[sid] = dict(_fields(meta)).get(2, b"").decode()
        if not trace_reduce.CHIP_PLANE.match(name):
            continue
        tf_op = {sid for sid, s in stat_names.items() if s == _TF_OP}
        ops: Dict[int, Tuple[str, str]] = {}
        for mid, meta in metas:
            text, op = "", ""
            for f, v in _fields(meta):
                if f == 2:
                    text = v.decode(errors="replace")
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        if 5 in stat:
                            op = stat[5].decode(errors="replace")
                        elif 7 in stat:      # a reference to a stat name
                            op = stat_names.get(stat[7], "")
            ops[mid] = (text, op)
        events: List[trace_reduce.Interval] = []
        for line in lines:
            fields = list(_fields(line))
            if dict(fields).get(2, b"") != trace_reduce.OPS_LINE.encode():
                continue
            t0 = dict(fields).get(3, 0)
            for f, v in fields:
                if f == 4:
                    ev = dict(_fields(v))
                    start = t0 + ev.get(2, 0) / 1e3
                    events.append((start, start + ev.get(3, 0) / 1e3,
                                   ev.get(1, 0)))
        out.append((name, events, ops))
    return out


# -- device time per scope ---------------------------------------------------

def _prefixes(path: str) -> List[str]:
    parts = path.split("/")
    return ["/".join(parts[:i]) for i in range(1, len(parts) + 1)]


def _common(paths: Sequence[str]) -> str:
    split = [p.split("/") for p in paths]
    out = []
    for parts in zip(*split):
        if any(x != parts[0] for x in parts):
            break
        out.append(parts[0])
    return "/".join(out)


def infer_scopes(ops: Sequence[trace_reduce.Interval],
                 scope: Dict[object, str]) -> Dict[object, str]:
    """``scope`` with each unscoped op that holds scoped ops (a loop and
    its body) given the scope path those ops share."""
    ops = sorted(ops)
    starts = [iv[0] for iv in ops]
    out = dict(scope)
    for s, e, name in ops:
        if out.get(name, UNSCOPED) != UNSCOPED or e <= s:
            continue
        inside = []
        for j in range(bisect.bisect_left(starts, s), len(ops)):
            if ops[j][0] >= e:
                break
            if ops[j][1] <= e and scope.get(ops[j][2], UNSCOPED):
                inside.append(scope[ops[j][2]])
        if inside:
            out[name] = _common(inside)
    return out


def device_scopes(chips: Sequence[Sequence[trace_reduce.Interval]],
                  scopes: Sequence[Dict[object, str]], lo: float, hi: float
                  ) -> Tuple[Dict[str, float], float]:
    """Seconds under each scope path, and under any scope, averaged over
    chips.  ``chips`` holds each chip's op intervals (ns, keyed by the
    op's metadata id), ``scopes`` each chip's key -> scope path."""
    per: Dict[str, float] = {}
    scoped = 0.0
    for ops, scope in zip(chips, scopes):
        buckets: Dict[str, list] = {}
        for iv in ops:
            path = scope.get(iv[2], UNSCOPED)
            if path == UNSCOPED:
                continue
            for p in _prefixes(path):
                buckets.setdefault(p, []).append(iv)
            buckets.setdefault(UNSCOPED, []).append(iv)
        for p, ivs in buckets.items():
            s = sum(e - s for s, e in trace_reduce.merge(ivs, lo, hi)) / 1e9
            if p == UNSCOPED:
                scoped += s
            else:
                per[p] = per.get(p, 0.0) + s
    n = max(len(chips), 1)
    return {p: s / n for p, s in per.items()}, scoped / n


def reduce(path: str) -> dict:
    """:func:`bench.trace_reduce.reduce` of the trace at ``path``, with its
    device time named by scope and its idle time by program span."""
    pd = trace_reduce.load(path)
    summary = trace_reduce.reduce(pd)
    ann = trace_reduce.host_spans(pd, prefix=PREFIXES)
    lo, hi, _ = next(sp for sp in ann if sp[2] == trace_reduce.WINDOW)
    spans = [sp for sp in ann if sp[2] != trace_reduce.WINDOW]
    with open(path, "rb") as f:
        planes = chip_ops(f.read())
    chips = [events for _, events, _ in planes]
    scopes = [infer_scopes(events, {mid: scope_of(op)
                                    for mid, (_, op) in ops.items()})
              for _, events, ops in planes]
    per, scoped = device_scopes(chips, scopes, lo, hi)

    per_op: Dict[Tuple[str, str], float] = {}
    for (_, events, ops), scope in zip(planes, scopes):
        for s, e, mid in events:
            d = (min(e, hi) - max(s, lo)) / 1e9
            if d > 0:
                key = (trace_reduce.op_name(ops.get(mid, ("?", ""))[0]),
                       scope.get(mid, UNSCOPED))
                per_op[key] = per_op.get(key, 0.0) + d / len(planes)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]

    idle: Dict[str, float] = {}
    first = trace_reduce.device_ops(pd)[:1]   # as the summary's idle gaps
    busy = trace_reduce.merge(first[0], lo, hi) if first else []
    for s, e in trace_reduce.gaps(busy, lo, hi):
        cuts = sorted({s, e} | {p for sp in spans for p in sp[:2]
                                if s < p < e})
        for a, b in zip(cuts, cuts[1:]):
            name = trace_reduce._innermost(spans, (a + b) / 2)
            idle[name] = idle.get(name, 0.0) + (b - a) / 1e9
    idle_s = sum(idle.values())
    program = sum(v for k, v in idle.items() if k.startswith(PROGRAM))
    busy_s = summary["busy_s"]
    summary.update(
        device_scopes=[[k, v] for k, v in
                       sorted(per.items(), key=lambda kv: -kv[1])],
        scoped_share=scoped / busy_s if busy_s > 0 else None,
        scoped_ops=[[op, sc, v] for (op, sc), v in top_ops],
        idle_spans=trace_reduce._top(idle),
        idle_program_share=program / idle_s if idle_s > 0 else None,
    )
    return summary
