"""The program's results, read into the layout of :mod:`bench.reference`.

Only attributes are read here; nothing of the program is imported.  Every
buffer is cut to its live prefix, so padding never counts.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from .reference import SCALARS, WINDOWED


def _h(x) -> np.ndarray:
    return np.asarray(x)


def _group(g, aggs) -> List[np.ndarray]:
    n = int(g.n_groups)
    return ([_h(k)[:n].astype(np.int64) for k in g.keys]
            + [_h(g.aggs[a])[:n].astype(np.int64) for a in aggs])


def _unique(u) -> List[np.ndarray]:
    n = int(u.n_unique)
    return [_h(u.values)[:n].astype(np.int64),
            _h(u.counts)[:n].astype(np.int64)]


def scalars(q) -> Dict[str, int]:
    """The ten scalar queries of a ``QueryResults``."""
    return {k: int(getattr(q, k)) for k in SCALARS}


def challenge(res) -> dict:
    """Every output of a ``ChallengeResults``."""
    n_top = int(res.top.n_valid)
    return {
        "scalars": scalars(res.scalars),
        "vectors": {
            "links": _group(res.links, ("count", "packets")),
            "per_source": _group(res.per_source, ("count", "packets")),
            "per_destination": _group(res.per_destination,
                                      ("count", "packets")),
            "source_fanout": _group(res.source_fanout, ("count",)),
            "destination_fanin": _group(res.destination_fanin, ("count",)),
            "unique_sources": _unique(res.unique_sources),
            "unique_destinations": _unique(res.unique_destinations),
        },
        "top": [_h(res.top.src)[:n_top].astype(np.int64),
                _h(res.top.dst)[:n_top].astype(np.int64),
                _h(res.top.packets)[:n_top].astype(np.int64)],
        "windowed": {k: _h(res.windowed[k]).astype(np.int64)
                     if k in res.windowed else None for k in WINDOWED},
        "activity": _h(res.window_activity).astype(np.float64),
        "overlap": _h(res.window_ip_overlap).astype(np.int64),
    }


def stream_state(state) -> dict:
    """The fold state of a ``StreamState``: dictionary, links, activity and
    the counters (packets, batches, overflow)."""
    n, nl = int(state.n_ips), int(state.n_links)
    return {
        "dictionary": [_h(state.ip_values)[:n].astype(np.int64),
                       _h(state.ip_ids)[:n].astype(np.int64)],
        "links": [_h(state.win)[:nl].astype(np.int64),
                  _h(state.src)[:nl].astype(np.int64),
                  _h(state.dst)[:nl].astype(np.int64),
                  _h(state.packets)[:nl].astype(np.int64)],
        "activity": _h(state.activity).astype(np.float64),
        "counters": [int(state.n_packets), int(state.n_batches),
                     int(state.overflow)],
    }


def sketch(snap) -> dict:
    """The estimates of a ``SketchSnapshot``, named as the exact scalars."""
    nl, ns = int(snap.n_top_links), int(snap.n_top_talkers)
    return {
        "valid_packets": int(snap.n_packets),
        "n_unique_sources": float(snap.unique_sources),
        "n_unique_destinations": float(snap.unique_destinations),
        "unique_links": float(snap.unique_links),
        "max_link_packets": float(snap.max_link_packets),
        "max_source_packets": float(snap.max_source_packets),
        "top_link_src": _h(snap.top_link_src)[:nl].astype(np.int64),
        "top_link_dst": _h(snap.top_link_dst)[:nl].astype(np.int64),
        "top_link_packets": _h(snap.top_link_packets)[:nl].astype(np.int64),
        "top_talker_src": _h(snap.top_talker_src)[:ns].astype(np.int64),
        "top_talker_packets": _h(snap.top_talker_packets)[:ns].astype(
            np.int64),
    }
