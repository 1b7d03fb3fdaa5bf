# The group-by, windowed-histogram and overlap definitions follow
# src/repro/core/ref.py (ref_traffic_matrix, ref_run_all_queries,
# ref_windowed_histogram, ref_window_ip_overlap), rewritten over a weighted
# row table so that one function answers both the packet table of the batch
# job and the link table of the service; the rest is new.
"""Plain NumPy reference of every answer the cells check, and the comparison.

Nothing here imports the program.  Answers are plain dicts of NumPy arrays
and ints, in the layout :mod:`bench.answers` reads off the program's
results, so :func:`compare_challenge`, :func:`compare_stream_state` and
:func:`sketch_checks` count the entries in which two such dicts differ.

The controls (:func:`challenge_control`, :func:`stream_state_control`,
:func:`sketch_control`, :func:`shallow_cms_control`) are this reference
computed one precision below what the configuration states: link keys packed
into 32 bits instead of two 32-bit ids, counters held in int16 instead of
int32, and a Count-Min of one row instead of four.  Put in place of the
program's answers, each has to come out not correct.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SCALARS = ("valid_packets", "unique_links", "max_link_packets",
           "n_unique_sources", "n_unique_destinations", "n_unique_ips",
           "max_source_packets", "max_source_fanout",
           "max_destination_packets", "max_destination_fanin")
# the scalars that count packets: they scale with the number of passes
PACKET_SCALARS = ("valid_packets", "max_link_packets", "max_source_packets",
                  "max_destination_packets")
WINDOWED = ("valid_packets", "unique_links", "max_link_packets",
            "n_unique_sources", "n_unique_destinations", "max_source_packets",
            "max_source_fanout", "max_destination_packets",
            "max_destination_fanin")


def mix32(x) -> np.ndarray:
    """Murmur3's 32-bit finalizer (wrapping uint32 arithmetic)."""
    x = np.asarray(x).astype(np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def window_ids(ts, n_windows: int) -> np.ndarray:
    """Equal windows over the capture's whole time span, the last one closed."""
    ts = np.asarray(ts).astype(np.int64)
    if len(ts) == 0:
        return np.zeros(0, np.int32)
    t0 = ts.min()
    wlen = -(-int(ts.max() - t0 + 1) // n_windows)
    return np.minimum((ts - t0) // wlen, n_windows - 1).astype(np.int32)


def group(keys: Sequence[np.ndarray], w: np.ndarray):
    """Lexicographic group-by: (distinct keys, rows per group, sum of w)."""
    keys = [np.asarray(k, np.int64) for k in keys]
    n = len(keys[0])
    order = np.lexsort(keys[::-1])
    sk = [k[order] for k in keys]
    first = np.ones(n, bool)
    if n:
        change = np.zeros(n - 1, bool)
        for k in sk:
            change |= k[1:] != k[:-1]
        first[1:] = change
    seg = np.cumsum(first) - 1
    ng = int(seg[-1]) + 1 if n else 0
    count = np.bincount(seg, minlength=ng).astype(np.int64)
    sums = np.bincount(seg, weights=np.asarray(w, np.float64)[order],
                       minlength=ng)
    return [k[first] for k in sk], count, np.rint(sums).astype(np.int64)


def _scalars(src, dst, w) -> Dict[str, int]:
    (ls, ld), _, lp = group([src, dst], w)
    (ps,), _, pp = group([src], w)
    (pd,), _, dp = group([dst], w)
    _, fan_out, _ = group([ls], np.ones(len(ls)))
    _, fan_in, _ = group([ld], np.ones(len(ld)))
    top = lambda a: int(a.max()) if len(a) else 0
    return {
        "valid_packets": int(np.asarray(w, np.int64).sum()),
        "unique_links": len(ls),
        "max_link_packets": top(lp),
        "n_unique_sources": len(ps),
        "n_unique_destinations": len(pd),
        "n_unique_ips": len(np.unique(np.concatenate([src, dst]))),
        "max_source_packets": top(pp),
        "max_source_fanout": top(fan_out),
        "max_destination_packets": top(dp),
        "max_destination_fanin": top(fan_in),
    }


def scale_scalars(s: Dict[str, int], passes: int) -> Dict[str, int]:
    """The scalars after ``passes`` replays of the same rows."""
    return {k: v * passes if k in PACKET_SCALARS else v for k, v in s.items()}


def activity(win, src, w, n_windows: int, ip_bins: int) -> np.ndarray:
    """Per-window histogram of hashed sources, weighted by ``w``."""
    ids = (mix32(src) % np.uint32(ip_bins)).astype(np.int64)
    flat = np.asarray(win, np.int64) * ip_bins + ids
    return np.bincount(flat, weights=np.asarray(w, np.float64),
                       minlength=n_windows * ip_bins).reshape(n_windows,
                                                               ip_bins)


def window_overlap(src, dst, win, n_windows: int) -> np.ndarray:
    """overlap[w] = |distinct IPs active in window w and in w - 1|."""
    per = [np.unique(np.concatenate([src[win == w], dst[win == w]]))
           for w in range(n_windows)]
    out = np.zeros(n_windows, np.int64)
    for w in range(1, n_windows):
        out[w] = len(np.intersect1d(per[w], per[w - 1], assume_unique=True))
    return out


def challenge_answers(src, dst, win, w, *, n_windows: int, ip_bins: int,
                      k: int, activity_of: Optional[np.ndarray] = None
                      ) -> dict:
    """Every output of the challenge's analyze over a weighted row table.

    Rows are ``(src, dst, win)`` with weight ``w`` (1 per packet, or a link's
    packet count).  ``count`` aggregates count rows.  ``activity_of``
    replaces the computed activity histogram where the caller accumulates
    it elsewhere (the service keeps it over original IPs).
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    win = np.asarray(win, np.int64)
    w = np.asarray(w, np.int64)
    (ls, ld), lc, lp = group([src, dst], w)
    (ps,), pc, pp = group([src], w)
    (pd,), dc, dp = group([dst], w)
    (fs,), fc, _ = group([ls], np.ones(len(ls)))
    (fd,), dcn, _ = group([ld], np.ones(len(ld)))
    top = np.lexsort((ld, ls, -lp))[:k]
    windowed = {name: np.zeros(n_windows, np.int64) for name in WINDOWED}
    for wi in range(n_windows):
        m = win == wi
        if m.any():
            s = _scalars(src[m], dst[m], w[m])
            for name in WINDOWED:
                windowed[name][wi] = s[name]
    return {
        "scalars": _scalars(src, dst, w),
        "vectors": {
            "links": [ls, ld, lc, lp],
            "per_source": [ps, pc, pp],
            "per_destination": [pd, dc, dp],
            "source_fanout": [fs, fc],
            "destination_fanin": [fd, dcn],
            "unique_sources": [ps, pc],
            "unique_destinations": [pd, dc],
        },
        "top": [ls[top], ld[top], lp[top]],
        "windowed": windowed,
        "activity": (activity(win, src, w, n_windows, ip_bins)
                     if activity_of is None else activity_of),
        "overlap": window_overlap(src, dst, win, n_windows),
    }


def wrong(a, b) -> int:
    """Entries in which two arrays differ (every entry when shapes differ)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size, 1)
    return int(np.count_nonzero(a != b))


def _wrong_lists(a: List, b: List) -> int:
    return sum(wrong(x, y) for x, y in zip(a, b)) + abs(len(a) - len(b))


def compare_scalars(ref: Dict[str, int], got: Dict[str, int]) -> int:
    return sum(int(ref[k]) != int(got[k]) for k in SCALARS)


def compare_challenge(ref: dict, got: dict) -> Dict[str, int]:
    """Wrong entries per family of the challenge's outputs."""
    return {
        "scalars_wrong": compare_scalars(ref["scalars"], got["scalars"]),
        "vectors_wrong": sum(_wrong_lists(ref["vectors"][n], got["vectors"][n])
                             for n in ref["vectors"]),
        "windowed_wrong": sum(wrong(ref["windowed"][n], got["windowed"][n])
                              for n in WINDOWED),
        "overlap_wrong": wrong(ref["overlap"], got["overlap"]),
        "activity_wrong": wrong(ref["activity"], got["activity"]),
        "topk_wrong": _wrong_lists(ref["top"], got["top"]),
    }


# ---------------------------------------------------------------------------
# batch: the anonymization check
# ---------------------------------------------------------------------------

def anonymize_wrong(src, dst, anon_src, anon_dst) -> int:
    """Rows and ids at which the anonymized table is not a bijection of the
    capture's IP domain onto [0, n_unique_ips), applied to every row."""
    orig = np.concatenate([src, dst]).astype(np.int64)
    anon = np.concatenate([anon_src, anon_dst]).astype(np.int64)
    if orig.shape != anon.shape:
        return max(orig.size, anon.size)
    vals, inv = np.unique(orig, return_inverse=True)
    pi = np.full(len(vals), -1, np.int64)
    pi[inv] = anon
    not_a_function = int(np.count_nonzero(pi[inv] != anon))
    not_onto = int(np.count_nonzero(np.sort(pi) != np.arange(len(vals))))
    return not_a_function + not_onto


# ---------------------------------------------------------------------------
# service: the fold state
# ---------------------------------------------------------------------------

def stable_ids(src, dst) -> Tuple[np.ndarray, np.ndarray]:
    """(sorted distinct IPs, their ids in first-seen order, src before dst)."""
    inter = np.empty(2 * len(src), np.int64)
    inter[0::2] = src
    inter[1::2] = dst
    vals, first = np.unique(inter, return_index=True)
    return vals, np.argsort(np.argsort(first)).astype(np.int64)


def stream_state(src, dst, win, *, batches_per_pass: int, n_windows: int,
                 ip_bins: int) -> dict:
    """The exact fold state after one pass over the capture."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    vals, ids = stable_ids(src, dst)
    (lw, ls, ld), _, lp = group([win, src, dst], np.ones(len(src)))
    return {
        "dictionary": [vals, ids],
        "links": [lw, ls, ld, lp],
        "activity": activity(win, src, np.ones(len(src)), n_windows, ip_bins),
        "counters": [len(src), batches_per_pass, 0],
    }


def scale_state(one: dict, passes: int) -> dict:
    """The fold state after ``passes`` replays, from that after one."""
    lw, ls, ld, lp = one["links"]
    n, b, overflow = one["counters"]
    return {"dictionary": one["dictionary"], "links": [lw, ls, ld, lp * passes],
            "activity": one["activity"] * passes,
            "counters": [n * passes, b * passes, overflow]}


def snapshot_answers(state: dict, *, n_windows: int, ip_bins: int,
                     k: int) -> dict:
    """The snapshot's answers: the challenge over the state's link table in
    stable ids, with the state's own activity histogram."""
    vals, ids = state["dictionary"]
    lw, ls, ld, lp = state["links"]
    sid = ids[np.searchsorted(vals, ls)]
    did = ids[np.searchsorted(vals, ld)]
    return challenge_answers(sid, did, lw, lp, n_windows=n_windows,
                             ip_bins=ip_bins, k=k,
                             activity_of=state["activity"])


def compare_stream_state(ref: dict, got: dict) -> Dict[str, int]:
    return {
        "dictionary_wrong": _wrong_lists(ref["dictionary"], got["dictionary"]),
        "links_wrong": _wrong_lists(ref["links"], got["links"]),
        "fold_activity_wrong": wrong(ref["activity"], got["activity"]),
        "counters_wrong": wrong(ref["counters"], got["counters"]),
    }


# ---------------------------------------------------------------------------
# sketch: estimates against their stated bounds
# ---------------------------------------------------------------------------

def exact_counts(src, dst) -> dict:
    """Exact per-link and per-source packet counts of one pass."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    (ls, ld), _, lp = group([src, dst], np.ones(len(src)))
    (ps,), _, pp = group([src], np.ones(len(src)))
    return {"scalars": _scalars(src, dst, np.ones(len(src))),
            "link_keys": ls << 32 | ld, "link_packets": lp,
            "src_keys": ps, "src_packets": pp}


def _lookup(keys, counts, q) -> np.ndarray:
    q = np.asarray(q, np.int64)
    if len(keys) == 0:
        return np.zeros(len(q), np.int64)
    pos = np.clip(np.searchsorted(keys, q), 0, len(keys) - 1)
    return np.where(keys[pos] == q, counts[pos], 0)


def sketch_checks(snaps: Sequence[dict], exact: dict, *, passes: Sequence[int],
                  cfg: dict) -> Dict[str, float]:
    """The worst reading over all snapshots of each stated sketch bound.

    ``snaps[i]`` was taken after ``passes[i]`` replays of the pass whose
    exact counts are ``exact``.  The bounds are the configuration's own:
    HLL within ``hll_sigma * 1.04 / sqrt(2^hll_p)``, maxima and listed heavy
    hitters within ``[true - N / (heavy_capacity + 1), true + e / width * N]``
    and ``[true, true + N / (heavy_capacity + 1)]``.
    """
    tol = cfg["hll_sigma"] * 1.04 / math.sqrt(1 << cfg["hll_p"])
    out = {"packets_gap": 0.0, "hll_err_share": 0.0, "max_gap_share": 0.0,
           "heavy_wrong": 0.0}
    for snap, p in zip(snaps, passes):
        true = scale_scalars(exact["scalars"], p)
        n = true["valid_packets"]
        eps_n = math.e / cfg["cms_width"] * n
        off = n / (cfg["heavy_capacity"] + 1)
        out["packets_gap"] = max(out["packets_gap"],
                                 abs(snap["valid_packets"] - n))
        for name in ("n_unique_sources", "n_unique_destinations",
                     "unique_links"):
            rel = abs(snap[name] - true[name]) / max(true[name], 1)
            out["hll_err_share"] = max(out["hll_err_share"], rel / tol)
        for name in ("max_link_packets", "max_source_packets"):
            gap = snap[name] - true[name]
            share = gap / eps_n if gap > 0 else -gap / off
            out["max_gap_share"] = max(out["max_gap_share"], share)
        got_l = _lookup(exact["link_keys"], exact["link_packets"],
                        np.asarray(snap["top_link_src"], np.int64) << 32
                        | np.asarray(snap["top_link_dst"], np.int64)) * p
        got_s = _lookup(exact["src_keys"], exact["src_packets"],
                        snap["top_talker_src"]) * p
        for est, tr in ((snap["top_link_packets"], got_l),
                        (snap["top_talker_packets"], got_s)):
            est = np.asarray(est, np.int64)
            out["heavy_wrong"] += int(np.count_nonzero(
                (est < tr) | (est > tr + off)))
    return out


# packets_gap and heavy_wrong are exact; hll_err_share is the configuration's
# own 4-sigma bound; max_gap_share lies between the largest reading of sound
# runs (0.0022) and the least of the controls (0.28)
SKETCH_LIMITS = {"packets_gap": 0, "hll_err_share": 1.0,
                 "max_gap_share": 0.03, "heavy_wrong": 0}


# ---------------------------------------------------------------------------
# controls: the reference one precision below the configuration
# ---------------------------------------------------------------------------

def collide_links(src, dst) -> Tuple[np.ndarray, np.ndarray]:
    """Rows' (src, dst) after keying links by a 32-bit hash of the pair: the
    links that share a hash become the smallest of them."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    key = mix32(mix32(src) ^ dst.astype(np.uint32)).astype(np.int64)
    order = np.lexsort((dst, src, key))
    k = key[order]
    first = np.ones(len(k), bool)
    first[1:] = k[1:] != k[:-1]
    rep = np.maximum.accumulate(np.where(first, np.arange(len(k)), 0))
    s2, d2 = np.empty_like(src), np.empty_like(dst)
    s2[order] = src[order][rep]
    d2[order] = dst[order][rep]
    return s2, d2


def challenge_control(src, dst, win, w, **kw) -> dict:
    s2, d2 = collide_links(src, dst)
    return challenge_answers(s2, d2, win, w, **kw)


def stream_state_control(src, dst, win, passes: int, **kw) -> dict:
    state = scale_state(stream_state(src, dst, win, **kw), passes)
    lw, ls, ld, lp = state["links"]
    s2, d2 = collide_links(ls, ld)
    (lw, ls, ld), _, lp = group([lw, s2, d2], lp)
    state["links"] = [lw, ls, ld, lp]
    return state


def sketch_truth(exact: dict, passes: int, k: int) -> dict:
    """The sketch tier's answers as an exact fold would give them."""
    s = scale_scalars(exact["scalars"], passes)
    lt = np.argsort(-exact["link_packets"], kind="stable")[:k]
    st = np.argsort(-exact["src_packets"], kind="stable")[:k]
    return {
        **{name: s[name] for name in (
            "valid_packets", "n_unique_sources", "n_unique_destinations",
            "unique_links", "max_link_packets", "max_source_packets")},
        "top_link_src": exact["link_keys"][lt] >> 32,
        "top_link_dst": exact["link_keys"][lt] & 0xFFFFFFFF,
        "top_link_packets": exact["link_packets"][lt] * passes,
        "top_talker_src": exact["src_keys"][st],
        "top_talker_packets": exact["src_packets"][st] * passes,
    }


def sketch_control(exact: dict, passes: int, k: int) -> dict:
    """Exact answers with every counter held in int16."""
    out = sketch_truth(exact, passes, k)
    for name, v in out.items():
        if name in ("top_link_packets", "top_talker_packets"):
            out[name] = np.asarray(v).astype(np.int16)
        elif not name.startswith("top_"):
            out[name] = int(np.int64(v).astype(np.int16))
    return out


def count_min_max(keys, counts, *, heavy: int, depth: int, width: int
                  ) -> int:
    """The largest Count-Min estimate among the ``heavy`` keys of most
    packets, the sketch an additive Count-Min of ``depth`` rows of ``width``
    cells over every key's count (keys hashed 64 bits wide, one salt a row)."""
    keys = np.asarray(keys, np.int64)
    counts = np.asarray(counts, np.int64)
    lo = (keys & 0xFFFFFFFF).astype(np.uint32)
    hi = (keys >> 32).astype(np.uint32)
    top = np.argsort(-counts, kind="stable")[:heavy]
    est = np.full(len(top), np.iinfo(np.int64).max)
    for r in range(depth):
        salt = np.uint32((0x9E3779B9 * (r + 1)) & 0xFFFFFFFF)
        col = (mix32(mix32(hi + salt) ^ lo) % np.uint32(width)).astype(
            np.int64)
        cells = np.bincount(col, weights=counts, minlength=width)
        est = np.minimum(est, np.rint(cells[col[top]]).astype(np.int64))
    return int(est.max()) if len(top) else 0


def shallow_cms_control(exact: dict, passes: int, k: int, *, heavy: int,
                        width: int, depth: int = 1) -> dict:
    """Exact answers but for the maxima, which a Count-Min of ``depth`` rows
    (below the configuration's) estimates over the heavy keys."""
    out = sketch_truth(exact, passes, k)
    kw = dict(heavy=heavy, depth=depth, width=width)
    out["max_link_packets"] = count_min_max(
        exact["link_keys"], exact["link_packets"] * passes, **kw)
    out["max_source_packets"] = count_min_max(
        exact["src_keys"], exact["src_packets"] * passes, **kw)
    return out
