"""Structured spans: the trace half of the telemetry layer (DESIGN.md §2.8).

The paper's claim is a *measured* one — per-phase speedups over an
end-to-end workload — so every layer of this repo needs one uniform way to
say "this region took this long, under these attributes".  A
:class:`Span` is that region: nestable (a thread-local stack tracks the
parent), exception-safe (the record is emitted even when the body raises,
with the error noted), and carrying both clocks — ``time.time()`` wall
epoch for correlation across processes and ``time.perf_counter()``
monotonic for durations (the same clock the legacy
``ChallengePhaseTimings`` used, which is what makes the derived view
bit-identical).

Records land in a bounded in-memory ring (old records are dropped, never
block the hot path) and, optionally, stream through a per-tracer ``sink``
callable as they close — ``launch/serve.py --metrics-out`` wires the sink
to an append-only JSONL file, giving a live event stream at no cost when
unused.  Every exported record is schema-versioned and stamped with the
run context (git sha, jax backend + version, pid) so two BENCH trajectories
are diffable without out-of-band notes.

Dependency-free by design: stdlib only; jax is probed lazily and absent
jax the backend stamp degrades to ``None`` instead of an import error.

Once jax is imported, two hooks join the record stream (installed on the
first span that opens after the import, never importing jax themselves):

* every span also opens a ``jax.profiler.TraceAnnotation`` named
  ``repro.<span path>``, so a profiler trace shows the program's own spans
  on its host timeline, on the same clock as the device ops;
* one ``jax.monitoring`` duration listener turns JAX's compile-path events
  into counter records: ``jit.trace_s`` (Python tracing to a jaxpr),
  ``jit.lower_s`` (jaxpr to an MLIR module) and ``jit.compile_s`` (backend
  compile, or a persistent-cache load), each valued in seconds, recorded as
  the step ends, and parented by the span open on the calling thread.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, IO, Iterable, List, Optional, Union

__all__ = [
    "SCHEMA_VERSION",
    "Span",
    "Tracer",
    "get_tracer",
    "reset_tracer",
    "span",
    "counter_event",
    "run_context",
    "export_jsonl",
    "read_jsonl",
    "jit_compile_count",
]

SCHEMA_VERSION = 1

_JSON_SCALARS = (str, int, float, bool, type(None))


def _jsonable(v: Any) -> Any:
    """Coerce one attribute value to something ``json.dumps`` accepts.

    Pytree-safe: jax/numpy 0-d arrays and scalars become Python numbers,
    small 1-d arrays become lists, everything else falls back to ``repr``
    — attaching a traced value to a span must never crash the traced
    program (and never forces a device sync: ``item()`` on a concrete
    array is host-side; abstract tracers hit the ``repr`` fallback).
    """
    if isinstance(v, _JSON_SCALARS):
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    item = getattr(v, "item", None)
    shape = getattr(v, "shape", None)
    if item is not None and shape is not None:
        try:
            if shape == ():
                return item()
            if len(shape) == 1 and shape[0] <= 64:
                return [_jsonable(x) for x in v.tolist()]
        except Exception:
            pass
    return repr(v)


_RUN_CONTEXT: Optional[Dict[str, Any]] = None


def _git_sha() -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5,
        )
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def run_context(refresh: bool = False) -> Dict[str, Any]:
    """The per-process provenance stamp every exported record carries.

    Computed once and cached (the git subprocess and jax import are not
    hot-path costs).  ``backend``/``jax_version`` are ``None`` when jax is
    unavailable — the telemetry layer itself has no hard dependency on it.
    """
    global _RUN_CONTEXT
    if _RUN_CONTEXT is None or refresh:
        backend = jax_version = None
        try:  # pragma: no cover - exercised wherever jax is installed
            import jax

            backend = jax.default_backend()
            jax_version = jax.__version__
        except Exception:
            pass
        _RUN_CONTEXT = {
            "git_sha": _git_sha(),
            "backend": backend,
            "jax_version": jax_version,
            "python": sys.version.split()[0],
            "pid": os.getpid(),
        }
    return dict(_RUN_CONTEXT)


@dataclasses.dataclass
class Span:
    """One timed region.  Live while open; frozen into a record on close."""

    name: str
    attrs: Dict[str, Any]
    t_wall: float            # epoch seconds at open (time.time)
    t_mono: float            # monotonic seconds at open (perf_counter)
    parent: Optional[str]    # dotted ancestor path, None at top level
    depth: int
    seq: int                 # per-tracer monotonically increasing id
    duration_s: Optional[float] = None   # set on close
    error: Optional[str] = None          # exception type name, if any

    @property
    def path(self) -> str:
        return f"{self.parent}/{self.name}" if self.parent else self.name

    def record(self) -> Dict[str, Any]:
        return {
            "schema_version": SCHEMA_VERSION,
            "kind": "span",
            "name": self.name,
            "path": self.path,
            "seq": self.seq,
            "t_wall": self.t_wall,
            "t_mono": self.t_mono,
            "duration_s": self.duration_s,
            "parent": self.parent,
            "depth": self.depth,
            "error": self.error,
            "attrs": {k: _jsonable(v) for k, v in self.attrs.items()},
        }


class Tracer:
    """A bounded ring of closed span/counter records + the open-span stack.

    The stack is thread-local (spans nest per thread; the Prefetcher
    thread's spans do not adopt the main thread's parent), the ring is
    shared and lock-guarded.  ``sink``, when set, receives each record
    dict as it is emitted — the live-stream hook.
    """

    def __init__(self, capacity: int = 4096,
                 sink: Optional[Callable[[Dict[str, Any]], None]] = None):
        self.capacity = capacity
        self.sink = sink
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._seq = 0

    # -- internals -----------------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _emit(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self._ring.append(rec)
        if self.sink is not None:
            try:
                self.sink(rec)
            except Exception:
                pass  # a broken sink must never take down the traced program

    # -- spans ---------------------------------------------------------------
    def span(self, name: str, **attrs: Any) -> "_SpanContext":
        return _SpanContext(self, name, attrs)

    def open_span(self, name: str, attrs: Dict[str, Any]) -> Span:
        st = self._stack()
        with self._lock:
            seq = self._seq
            self._seq += 1
        sp = Span(
            name=name, attrs=dict(attrs),
            t_wall=time.time(), t_mono=time.perf_counter(),
            parent=st[-1].path if st else None, depth=len(st), seq=seq,
        )
        st.append(sp)
        return sp

    def close_span(self, sp: Span, exc: Optional[BaseException] = None) -> Span:
        sp.duration_s = time.perf_counter() - sp.t_mono
        if exc is not None:
            sp.error = type(exc).__name__
        st = self._stack()
        if st and st[-1] is sp:
            st.pop()
        elif sp in st:          # defensive: close out of order, drop suffix
            del st[st.index(sp):]
        self._emit(sp.record())
        return sp

    # -- counter events ------------------------------------------------------
    def counter_event(self, name: str, value: Union[int, float] = 1,
                      **attrs: Any) -> Dict[str, Any]:
        """A point event (no duration): one schema-versioned record."""
        with self._lock:
            seq = self._seq
            self._seq += 1
        st = self._stack()
        rec = {
            "schema_version": SCHEMA_VERSION,
            "kind": "counter",
            "name": name,
            "seq": seq,
            "t_wall": time.time(),
            "t_mono": time.perf_counter(),
            "value": _jsonable(value),
            "parent": st[-1].path if st else None,
            "attrs": {k: _jsonable(v) for k, v in attrs.items()},
        }
        self._emit(rec)
        return rec

    # -- export --------------------------------------------------------------
    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


class _SpanContext:
    """Context manager handed out by :meth:`Tracer.span`."""

    def __init__(self, tracer: Tracer, name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self.span: Optional[Span] = None
        self._annotation = None

    def __enter__(self) -> Span:
        self.span = self._tracer.open_span(self._name, self._attrs)
        annotate = _jax_hooks()
        if annotate is not None:
            self._annotation = annotate(f"repro.{self.span.path}")
            self._annotation.__enter__()
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        self._tracer.close_span(self.span, exc)
        return False  # never swallow


# ---------------------------------------------------------------------------
# jax hooks: profiler annotations and JIT counters
# ---------------------------------------------------------------------------

_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_s",
    "/jax/core/compile/backend_compile_duration": "jit.compile_s",
}

_HOOK_LOCK = threading.Lock()
_ANNOTATION: Optional[Callable[[str], Any]] = None
_JIT_COMPILES = 0


def _on_jax_duration(event: str, secs: float, **kw: Any) -> None:
    global _JIT_COMPILES
    name = _JIT_EVENTS.get(event)
    if name is None:
        return
    if name == "jit.compile_s":
        _JIT_COMPILES += 1
    try:
        _GLOBAL.counter_event(name, secs, fun=kw.get("fun_name"))
    except Exception:
        pass  # telemetry must never fail a compile


def _jax_hooks() -> Optional[Callable[[str], Any]]:
    """``jax.profiler.TraceAnnotation`` once jax has been imported (None
    before), installing the JIT listener with it, once per process."""
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        with _HOOK_LOCK:
            if _ANNOTATION is None:
                import jax.monitoring
                import jax.profiler

                jax.monitoring.register_event_duration_secs_listener(
                    _on_jax_duration)
                _ANNOTATION = jax.profiler.TraceAnnotation
    return _ANNOTATION


def jit_compile_count() -> int:
    """Backend compiles (persistent-cache loads included) seen so far.

    Installs the hooks when jax is imported; a caller that compares two
    readings sees whether a compile happened in between."""
    _jax_hooks()
    return _JIT_COMPILES


# ---------------------------------------------------------------------------
# the process-global tracer
# ---------------------------------------------------------------------------

_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    return _GLOBAL


def reset_tracer(capacity: int = 4096,
                 sink: Optional[Callable[[Dict[str, Any]], None]] = None
                 ) -> Tracer:
    """Replace the global tracer (tests; serve's sink installation)."""
    global _GLOBAL
    _GLOBAL = Tracer(capacity=capacity, sink=sink)
    return _GLOBAL


def span(name: str, **attrs: Any) -> _SpanContext:
    """``with span("analyze", n=n) as sp: ...`` on the global tracer."""
    return _GLOBAL.span(name, **attrs)


def counter_event(name: str, value: Union[int, float] = 1,
                  **attrs: Any) -> Dict[str, Any]:
    return _GLOBAL.counter_event(name, value, **attrs)


# ---------------------------------------------------------------------------
# JSONL i/o
# ---------------------------------------------------------------------------

def export_jsonl(
    out: Union[str, IO[str]],
    records: Optional[Iterable[Dict[str, Any]]] = None,
    *,
    append: bool = False,
) -> int:
    """Write records (default: the global tracer's ring) as JSONL.

    The first line is a ``kind="run"`` header carrying the full
    :func:`run_context`; every following line is one span/counter record
    re-stamped with the same context fields (git sha, backend, jax
    version), so a single grepped line is self-describing.  Returns the
    number of lines written.
    """
    ctx = run_context()
    if records is None:
        records = _GLOBAL.records()
    header = {"schema_version": SCHEMA_VERSION, "kind": "run",
              "t_wall": time.time(), **ctx}
    lines = [header]
    for rec in records:
        lines.append({**rec, "git_sha": ctx["git_sha"],
                      "backend": ctx["backend"],
                      "jax_version": ctx["jax_version"]})
    text = "".join(json.dumps(ln, sort_keys=True) + "\n" for ln in lines)
    if isinstance(out, str):
        with open(out, "a" if append else "w") as f:
            f.write(text)
    else:
        out.write(text)
    return len(lines)


def read_jsonl(path_or_text: str) -> List[Dict[str, Any]]:
    """Parse a JSONL export (a path, or the raw text itself)."""
    if "\n" not in path_or_text and os.path.exists(path_or_text):
        with open(path_or_text) as f:
            text = f.read()
    else:
        text = path_or_text
    out = []
    for line in text.splitlines():
        line = line.strip()
        if line:
            out.append(json.loads(line))
    return out
