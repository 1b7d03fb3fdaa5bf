"""sketch_snapshot_s: mean host wall of the ``snapshot/sketch`` span
(``StreamEngine.snapshot`` -> ``core/sketch.snapshot_sketch``, which runs
eagerly op by op and ends with host values) per window snapshot of the
sketch-tier service.

The spans are read from the program's own tracer, in this process: the
window's snapshots are the last ``passes`` before the traced one.  None
where the program records no such span."""


def read(obs):
    passes = obs.get("window", {}).get("passes")
    if not passes:
        return None
    try:
        from repro.obs import get_tracer
    except ImportError:
        return None
    walls = [r["duration_s"] for r in sorted(
        (r for r in get_tracer().records() if r.get("kind") == "span"
         and r.get("name") == "sketch" and r.get("parent") == "snapshot"),
        key=lambda r: r["seq"])]
    if obs.get("trace") is not None:
        walls = walls[:-1]
    walls = walls[-passes:]
    if len(walls) != passes:
        return None
    return sum(walls) / passes
