"""dispatch_s.batch: mean host seconds per window pass of the batch
pipeline spent tracing, lowering, and compiling or loading its programs:
the union of the intervals of the program's ``jit.trace_s``, ``jit.lower_s``
and ``jit.compile_s`` counters (``repro.obs``; each a duration that ends
when its record is made) inside each window pass's ``challenge`` span.

The counters are read from the program's own tracer, in this process: the
window passes are the last ``passes`` ``challenge`` spans before the traced
one.  None where the program records no such counters."""

JIT = ("jit.trace_s", "jit.lower_s", "jit.compile_s")


def read(obs):
    passes = obs.get("window", {}).get("passes")
    if not passes:
        return None
    try:
        from repro.obs import get_tracer
    except ImportError:
        return None
    recs = get_tracer().records()
    jit = [(r["t_mono"] - r["value"], r["t_mono"]) for r in recs
           if r.get("kind") == "counter" and r.get("name") in JIT]
    runs = sorted((r for r in recs if r.get("kind") == "span"
                   and r.get("name") == "challenge" and r.get("parent") is None),
                  key=lambda r: r["seq"])
    if obs.get("trace") is not None:
        runs = runs[:-1]
    runs = runs[-passes:]
    if not jit or len(runs) != passes:
        return None
    total = 0.0
    for r in runs:
        lo, hi = r["t_mono"], r["t_mono"] + r["duration_s"]
        end = lo
        for s, e in sorted(iv for iv in jit if lo <= iv[1] <= hi):
            s = max(s, end)
            if e > s:
                total += e - s
                end = e
    return total / passes
