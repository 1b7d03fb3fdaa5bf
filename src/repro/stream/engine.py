"""Incremental analytics engine over packet micro-batches (DESIGN.md §6).

``StreamEngine`` consumes micro-batches (plq row-group chunks via
``data.pipeline.Prefetcher``, or any ``(src, dst, win)`` column slices) and
folds each one into a :class:`repro.stream.state.StreamState`:

  1. **dictionary update** — batch-distinct IPs not yet in the persistent
     anonymization dictionary get the next free stable ids, and the sorted
     dictionary is rebuilt by one validity-masked merge sort;
  2. **link accumulation** — the batch's ``(window, src, dst)`` group-by is
     merged into the accumulated windowed traffic matrix by one concat +
     group-by (the engine's sort-based replacement for a hash-table upsert);
  3. **activity accumulation** — the batch's per-window hashed-source
     histogram folds into the running accumulator through the kernels.ops
     accumulate path (``windowed_histogram(..., init=state.activity)``).

All 14 Table III queries are answerable *at any point* from the state alone
(``snapshot()``), with results identical to a one-shot batch run over the
packets seen so far: the snapshot routes the accumulated link table —
weighted by per-link packet sums — through the same ``challenge.analyze``
program the batch pipeline uses, so equivalence holds by construction
(weighted links are query-equivalent to the packets they summarize).

``merge_states`` combines two independently built states (host-sharded
streaming); ``snapshot(distributed=True)`` instead merges one state's link
table through the ``repro.dist`` shard_map path across local devices.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..challenge.pipeline import ChallengeResults
from ..challenge.pipeline import analyze as challenge_analyze
from ..challenge.pipeline import distributed_scalar_queries
from ..core.ops import factorize, groupby_aggregate, isin, mix32, multi_key_sort
from ..core.plan import unique_concat
from ..core.sketch import (
    SketchConfig,
    SketchSnapshot,
    SketchState,
    init_sketch,
    merge_sketches,
    snapshot_sketch,
    update_sketch,
)
from ..core.sparse import ewise_union, from_coo
from ..core.table import Table
from ..data.faults import IngestHealth
from ..data.pipeline import Prefetcher
from ..data.plq import read_plq_chunks
from ..kernels.ops import windowed_histogram
from ..obs import get_registry, jit_compile_count
from ..obs import span as obs_span
from .state import StreamState, init_state

__all__ = [
    "StreamConfig",
    "StreamEngine",
    "StreamBatchTimings",
    "StreamSnapshot",
    "update_state",
    "update_state_naive",
    "merge_states",
    "link_table",
    "anonymization_mapping",
    "stream_plq",
    "steady_state",
]

_TIER_ORDER = {"exact": 0, "both": 1, "sketch": 2}

_I32_MAX = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static capacities + query parameters of one stream engine.

    ``link_capacity`` bounds the distinct ``(window, src, dst)`` groups the
    state can hold and ``ip_capacity`` the distinct IPs; exceeding either is
    *counted* in ``state.overflow`` (reported, never silent).  Results are
    exact iff overflow == 0: dropped links undercount, and dropped
    dictionary entries additionally alias their IPs onto surviving stable
    ids at snapshot time — an overflowed state's results are unreliable,
    not merely lower bounds.  ``batch_capacity`` is the static micro-batch
    buffer size: re-jitting happens per capacity, never per batch occupancy.

    ``tier`` selects the analytics substrate(s) every batch folds into
    (DESIGN.md §2.6): ``"exact"`` is the CSR state above; ``"sketch"``
    replaces it with the bounded-memory approximate tier
    (:mod:`repro.core.sketch` — never overflows, answers carry error
    bounds); ``"both"`` runs the tiers side by side (the validation mode:
    the exact path is the sketch path's oracle while it still fits).
    """

    batch_capacity: int
    link_capacity: int
    ip_capacity: Optional[int] = None    # default: 2 * link_capacity
    n_windows: int = 8
    ip_bins: int = 1024
    top_k: int = 10
    backend: str = "auto"                # histogram kernel dispatch
    tier: str = "exact"                  # exact | sketch | both
    sketch: Optional[SketchConfig] = None  # geometry of the approximate tier

    def __post_init__(self):
        for f in ("batch_capacity", "link_capacity", "ip_capacity",
                  "n_windows", "ip_bins", "top_k"):
            if getattr(self, f) is not None and getattr(self, f) < 1:
                raise ValueError(f"{f} must be >= 1")
        if self.tier not in ("exact", "sketch", "both"):
            raise ValueError(
                f"tier must be exact|sketch|both, got {self.tier!r}"
            )

    @property
    def ips(self) -> int:
        # each link contributes at most 2 distinct IPs
        return self.ip_capacity or 2 * self.link_capacity

    @property
    def exact_enabled(self) -> bool:
        return self.tier in ("exact", "both")

    @property
    def sketch_enabled(self) -> bool:
        return self.tier in ("sketch", "both")

    @property
    def sketch_config(self) -> SketchConfig:
        return self.sketch if self.sketch is not None else SketchConfig()


# ---------------------------------------------------------------------------
# the state transition (pure, jittable, donates the old state)
# ---------------------------------------------------------------------------

def _rank_among(order: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = position of ``order[i]`` among the masked entries sorted
    ascending (garbage where ``~mask``).  Orders must be distinct."""
    cap = order.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    (_,), (slot,) = multi_key_sort(
        [order.astype(jnp.int32)], [idx], valid_mask=mask
    )
    return jnp.zeros((cap,), jnp.int32).at[slot].set(idx)


def _merge_dictionary(
    values: jnp.ndarray,
    ids: jnp.ndarray,
    n: jnp.ndarray,
    cand_values: jnp.ndarray,
    cand_new: jnp.ndarray,
    cand_order: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Insert candidate IPs (sorted distinct, ``cand_new`` mask) into the
    dictionary.  New entries get ids ``n, n+1, ...`` following ascending
    ``cand_order`` (first-appearance positions — the rule that makes ids
    invariant to how the stream is cut into micro-batches); existing ids
    never change (the stability contract).  Returns ``(values, ids, n,
    dropped)`` with ``dropped`` > 0 iff capacity filled.
    """
    cap = values.shape[0]
    n_new = jnp.sum(cand_new).astype(jnp.int32)
    fresh = n + _rank_among(cand_order, cand_new)
    cat_v = jnp.concatenate([values, cand_values.astype(jnp.int32)])
    cat_i = jnp.concatenate([ids, fresh.astype(jnp.int32)])
    cat_ok = jnp.concatenate(
        [jnp.arange(cap, dtype=jnp.int32) < n, cand_new]
    )
    (sv,), (si,) = multi_key_sort([cat_v], [cat_i], valid_mask=cat_ok)
    total = n + n_new
    n2 = jnp.minimum(total, cap)
    live = jnp.arange(cap, dtype=jnp.int32) < n2
    return (
        jnp.where(live, sv[:cap], _I32_MAX),
        jnp.where(live, si[:cap], 0),
        n2,
        (total - n2).astype(jnp.int32),
    )


def _merge_links(
    state: StreamState,
    keys: Sequence[jnp.ndarray],
    packets: jnp.ndarray,
    valid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merge incoming distinct links into the accumulated link table: one
    concat + (win, src, dst) group-by with packet sums — the sort-based
    upsert.  Truncation on overflow keeps the lexicographically smallest
    groups (deterministic) and is counted, never silent."""
    cap = state.link_capacity
    state_valid = jnp.arange(cap, dtype=jnp.int32) < state.n_links
    merged = groupby_aggregate(
        [jnp.concatenate([state.win, keys[0]]),
         jnp.concatenate([state.src, keys[1]]),
         jnp.concatenate([state.dst, keys[2]])],
        {"packets": (jnp.concatenate([state.packets, packets]), "sum")},
        valid_mask=jnp.concatenate([state_valid, valid]),
        count_name=None,
    )
    n2 = jnp.minimum(merged.n_groups, cap)
    dropped = (merged.n_groups - n2).astype(jnp.int32)
    live = jnp.arange(cap, dtype=jnp.int32) < n2
    return (
        jnp.where(live, merged.keys[0][:cap], _I32_MAX),
        jnp.where(live, merged.keys[1][:cap], _I32_MAX),
        jnp.where(live, merged.keys[2][:cap], _I32_MAX),
        jnp.where(live, merged.aggs["packets"][:cap].astype(jnp.int32), 0),
        n2,
        dropped,
    )


def _fold_dictionary_and_activity(
    state: StreamState,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    win: jnp.ndarray,
    valid: jnp.ndarray,
    n_valid: jnp.ndarray,
    backend: str,
):
    """Steps 1 and 3 of the state transition, shared by both link paths.

    1. persistent anonymization dictionary.  Batch-distinct IPs carry
    their first-appearance position (row-major, src before dst) so new
    ids follow first-seen order — invariant to micro-batch boundaries.
    Candidate extraction is the plan's packed concat sort
    (core/plan.unique_concat, DESIGN.md §2.3): one single-operand uint64
    sort over the compacted endpoint union, in place of the pre-plan
    3-operand (validity, ip, pos) comparator sort over the masked concat.

    3. per-window activity accumulator (kernels.ops accumulate path).
    Bins hash the ORIGINAL IP so independently built states merge by
    addition; the (lossy) sketch does not expose ids — see DESIGN.md §6.
    """
    with jax.named_scope("dictionary"):
        rows = jnp.arange(src.shape[0], dtype=jnp.int32)
        bu = unique_concat(
            src, dst, n_valid,
            positions=jnp.concatenate([2 * rows, 2 * rows + 1]),
            count_name=None,
        )
        known = isin(bu.keys[0], state.ip_values, state.n_ips,
                     n_valid=bu.n_groups)
        new = bu.mask() & ~known
        dictionary = _merge_dictionary(
            state.ip_values, state.ip_ids, state.n_ips,
            bu.keys[0], new, bu.aggs["first_pos"],
        )
    with jax.named_scope("activity"):
        act_ids = jnp.where(
            valid, (mix32(src) % jnp.uint32(state.ip_bins)).astype(jnp.int32),
            -1,
        )
        activity = windowed_histogram(
            win, act_ids, state.n_windows, state.ip_bins,
            weights=valid.astype(jnp.float32),
            init=state.activity, backend=backend,
        )
    return dictionary, activity


def update_state(
    state: StreamState,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    win: jnp.ndarray,
    n_valid: jnp.ndarray,
    *,
    backend: str = "auto",
) -> StreamState:
    """Fold one micro-batch (padded to ``batch_capacity``) into the state.

    2. accumulated windowed traffic matrix: ONE ``core.sparse.from_coo``
    over the state's CSR entries ++ the raw batch rows — duplicate collapse
    under the plus monoid is simultaneously the batch's (win, src, dst)
    group-by AND the upsert into the accumulated matrix, so the link path
    costs one sort where the pre-CSR path (:func:`update_state_naive`)
    paid two.  Overflow (groups beyond ``link_capacity``) is counted by
    ``from_coo``, never silent.

    Device scopes: ``update_state/dictionary``, ``/links``, ``/activity``.
    """
    with jax.named_scope("update_state"):
        n_windows = state.n_windows
        n_valid = jnp.asarray(n_valid, jnp.int32)
        src = src.astype(jnp.int32)
        dst = dst.astype(jnp.int32)
        win = jnp.clip(win.astype(jnp.int32), 0, n_windows - 1)
        t = Table(columns={"src": src, "dst": dst}, n_valid=n_valid)
        valid = t.valid_mask()

        (ip_values, ip_ids, n_ips, ov_ips), activity = \
            _fold_dictionary_and_activity(
                state, src, dst, win, valid, n_valid, backend
            )

        with jax.named_scope("links"):
            links, ov_links = from_coo(
                [jnp.concatenate([state.win, win]),
                 jnp.concatenate([state.src, src])],
                jnp.concatenate([state.dst, dst]),
                jnp.concatenate([state.packets,
                                 jnp.ones((src.shape[0],), jnp.int32)]),
                valid_mask=jnp.concatenate([state.links.entry_mask(), valid]),
                op="plus",
                nnz_capacity=state.link_capacity,
            )

        return StreamState(
            ip_values=ip_values, ip_ids=ip_ids, n_ips=n_ips,
            links=links,
            activity=activity,
            n_packets=state.n_packets + n_valid,
            n_batches=state.n_batches + 1,
            overflow=state.overflow + ov_ips + ov_links,
        )


def update_state_naive(
    state: StreamState,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    win: jnp.ndarray,
    n_valid: jnp.ndarray,
    *,
    backend: str = "auto",
) -> StreamState:
    """Pre-CSR link path, kept as the A/B baseline: batch group-by, then a
    second concat group-by merging it into the accumulated flat link table
    (:func:`_merge_links`), then a pack into the CSR state layout.  Produces
    a bit-identical ``StreamState`` to :func:`update_state` — asserted by
    tests/test_stream.py — at one extra sort per batch.  Same device
    scopes as :func:`update_state`.
    """
    with jax.named_scope("update_state"):
        n_windows = state.n_windows
        n_valid = jnp.asarray(n_valid, jnp.int32)
        src = src.astype(jnp.int32)
        dst = dst.astype(jnp.int32)
        win = jnp.clip(win.astype(jnp.int32), 0, n_windows - 1)
        t = Table(columns={"src": src, "dst": dst}, n_valid=n_valid)
        valid = t.valid_mask()

        (ip_values, ip_ids, n_ips, ov_ips), activity = \
            _fold_dictionary_and_activity(
                state, src, dst, win, valid, n_valid, backend
            )

        with jax.named_scope("links"):
            bl = groupby_aggregate(
                [win, src, dst],
                {"packets": (jnp.ones((src.shape[0],), jnp.int32), "sum")},
                n_valid=n_valid,
                count_name=None,
            )
            w2, s2, d2, pk2, n_links, ov_links = _merge_links(
                state, bl.keys, bl.aggs["packets"], bl.mask()
            )
            # pack the (already distinct, lex-sorted) flat table into the
            # CSR layout
            links, _ = from_coo([w2, s2], d2, pk2, n_valid=n_links,
                                op="plus")

        return StreamState(
            ip_values=ip_values, ip_ids=ip_ids, n_ips=n_ips,
            links=links,
            activity=activity,
            n_packets=state.n_packets + n_valid,
            n_batches=state.n_batches + 1,
            overflow=state.overflow + ov_ips + ov_links,
        )


def merge_states(a: StreamState, b: StreamState) -> StreamState:
    """Merge two independently built shard states (same capacities).

    Exact for links, scalars and activity: the accumulated matrices merge
    by ``core.sparse.ewise_union`` under the plus monoid (coincident
    ``(win, src, dst)`` coordinates add; overflow counted).  ``b``'s IPs
    unknown to ``a`` get fresh ids continuing ``a``'s sequence in ``b``'s
    first-seen order, so the merge is associative/commutative up to id
    relabeling — see state.py.
    """
    if (a.link_capacity != b.link_capacity
            or a.ip_capacity != b.ip_capacity
            or a.activity.shape != b.activity.shape):
        raise ValueError(
            "merge_states requires equal static capacities and "
            f"(n_windows, ip_bins): {a.link_capacity}/{a.ip_capacity}/"
            f"{a.activity.shape} vs {b.link_capacity}/{b.ip_capacity}/"
            f"{b.activity.shape}"
        )
    known = isin(b.ip_values, a.ip_values, a.n_ips, n_valid=b.n_ips)
    new = (jnp.arange(b.ip_capacity, dtype=jnp.int32) < b.n_ips) & ~known
    ip_values, ip_ids, n_ips, ov_ips = _merge_dictionary(
        a.ip_values, a.ip_ids, a.n_ips, b.ip_values, new, b.ip_ids
    )
    links, ov_links = ewise_union(
        a.links, b.links, op="plus",
        nnz_capacity=a.link_capacity, row_capacity=a.link_capacity,
    )
    return StreamState(
        ip_values=ip_values, ip_ids=ip_ids, n_ips=n_ips,
        links=links,
        activity=a.activity + b.activity,
        n_packets=a.n_packets + b.n_packets,
        n_batches=a.n_batches + b.n_batches,
        overflow=a.overflow + b.overflow + ov_ips + ov_links,
    )


# ---------------------------------------------------------------------------
# queries over the state
# ---------------------------------------------------------------------------

def link_table(state: StreamState) -> Table:
    """The accumulated windowed traffic matrix as an anonymized packet table.

    One row per distinct ``(window, src, dst)`` with ``n_packets`` weights;
    src/dst are the dictionary's stable ids.  Because every challenge query
    weights rows by ``n_packets``, this table is query-equivalent to the
    full packet stream seen so far.
    """
    cap = state.link_capacity
    live = jnp.arange(cap, dtype=jnp.int32) < state.n_links
    sid = state.ip_ids[factorize(state.src, state.ip_values)]
    did = state.ip_ids[factorize(state.dst, state.ip_values)]
    return Table(
        columns={
            "win": jnp.where(live, state.win, 0),
            "src": jnp.where(live, sid, 0),
            "dst": jnp.where(live, did, 0),
            "n_packets": jnp.where(live, state.packets, 0),
        },
        n_valid=state.n_links,
    )


def _snapshot_results(
    state: StreamState, *, top_k: int, backend: str
) -> ChallengeResults:
    with jax.named_scope("link_table"):
        table = link_table(state)
    res = challenge_analyze(
        table, n_windows=state.n_windows, ip_bins=state.ip_bins,
        k=top_k, backend=backend,
    )
    # the accumulated activity (original-IP bins, mergeable) replaces the
    # snapshot recomputation (stable-id bins) — same sketch family, but only
    # the accumulated one adds across shards; see state.py.
    return dataclasses.replace(res, window_activity=state.activity)


def anonymization_mapping(state: StreamState) -> Tuple[np.ndarray, np.ndarray]:
    """Host copy of the dictionary: ``(original_ips, stable_ids)`` (live rows)."""
    n = int(state.n_ips)
    return np.asarray(state.ip_values)[:n], np.asarray(state.ip_ids)[:n]


@dataclasses.dataclass
class StreamSnapshot:
    """Point-in-time query answer over everything streamed so far.

    ``results`` is the exact tier's answer (None when ``tier="sketch"``);
    ``sketch`` the approximate tier's (None when ``tier="exact"``).
    ``n_links``/``n_ips``/``overflow`` are exact-tier facts and are None
    when that tier is disabled — a sketch-only snapshot must not dress
    the never-updated init state up as exact zeros.

    ``tier`` is the tier *active at snapshot time* — under the
    graceful-degradation policy (DESIGN.md §2.7) it can differ from the
    configured tier, and ``health.degraded_to``/``degraded_at_batch``
    record where the switch happened (never silent).  ``health`` is the
    ingest-path ledger (:class:`repro.data.faults.IngestHealth`):
    quarantined copies, retries, duplicates dropped, batches replayed,
    crashes recovered, lost batches.
    """

    results: Optional[ChallengeResults]
    n_packets: int
    n_batches: int
    n_links: Optional[int]  # None when the exact tier is disabled
    n_ips: Optional[int]    # None when the exact tier is disabled
    overflow: Optional[int] # > 0 => exact results unreliable (never
                            # silent): dropped links undercount, dropped
                            # dictionary entries alias ids — StreamConfig.
                            # None when the exact tier is disabled.
    sketch: Optional[SketchSnapshot] = None
    tier: str = "exact"     # the tier active when this snapshot was taken
    health: Optional[IngestHealth] = None

    @property
    def reliable(self) -> bool:
        """True iff nothing was lost: the exact tier's overflow counter is
        zero (or that tier is off entirely — the sketch tier cannot
        overflow; its answers are instead bounded by ``sketch.bounds``)
        AND the ingest path dropped no batch past its retry budget."""
        overflowed = self.overflow is not None and self.overflow != 0
        lost = self.health is not None and self.health.lost_batches > 0
        return not overflowed and not lost


# ---------------------------------------------------------------------------
# per-batch timings (steady-state protocol, docs/METHODOLOGY.md)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamBatchTimings:
    """Wall seconds of one ingest.  ``compile=True`` batches carry the
    trace+compile cost and are excluded from steady-state summaries —
    the same protocol as ``ChallengePhaseTimings.compile_s``.  ``stream_plq``
    sets it when JAX compiled or loaded a program during the batch
    (``repro.obs.jit_compile_count``), so a warm engine has none."""

    n_packets: int
    prep_s: float        # host: cast + window slice + padding
    transfer_s: float    # host->device (explicit only when time_phases)
    update_s: float      # the jitted state transition
    total_s: float
    compile: bool = False


def steady_state(timings: Sequence[StreamBatchTimings]) -> Dict[str, float]:
    """Aggregate steady-state (compile-excluded) per-batch walls."""
    steady = [t for t in timings if not t.compile]
    if not steady:
        return {"batches": 0.0, "batch_s": 0.0, "packets_per_s": 0.0,
                "prep_s": 0.0, "transfer_s": 0.0, "update_s": 0.0}
    n = len(steady)
    pk = sum(t.n_packets for t in steady)
    tot = sum(t.total_s for t in steady)
    return {
        "batches": float(n),
        "batch_s": tot / n,
        "packets_per_s": pk / tot if tot > 0 else float("inf"),
        "prep_s": sum(t.prep_s for t in steady) / n,
        "transfer_s": sum(t.transfer_s for t in steady) / n,
        "update_s": sum(t.update_s for t in steady) / n,
    }


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

# Jitted entry points are cached at module level, keyed by the static
# arguments that shape the trace.  A supervised service loop constructs a
# fresh StreamEngine after every crash/restore cycle (stream/recovery.py);
# per-engine ``jax.jit`` wrappers would re-trace and re-compile the update
# on every restart, turning recovery wall time into compile time.  With the
# cache, restart N reuses restart 0's executable.

@functools.lru_cache(maxsize=None)
def _jitted_update(backend: str, donate: bool):
    return jax.jit(
        functools.partial(update_state, backend=backend),
        donate_argnums=(0,) if donate else (),
    )


@functools.lru_cache(maxsize=None)
def _jitted_snapshot(top_k: int, backend: str):
    return jax.jit(
        functools.partial(_snapshot_results, top_k=top_k, backend=backend)
    )


@functools.lru_cache(maxsize=None)
def _jitted_sketch_update(backend: str, donate: bool):
    return jax.jit(
        functools.partial(update_sketch, backend=backend),
        donate_argnums=(0,) if donate else (),
    )


class StreamEngine:
    """Stateful driver around the pure state transition.

    ``ingest`` dispatches asynchronously (JAX's async dispatch): the host
    returns before the device finishes, so preparing/transferring the next
    micro-batch overlaps the current update — double buffering falls out of
    calling ``ingest`` in a loop.  Off-CPU the old state's buffers are
    donated to the update, so the accumulated state lives in one set of
    device buffers.
    """

    def __init__(self, cfg: StreamConfig):
        self.cfg = cfg
        self._state = init_state(
            cfg.link_capacity, cfg.ips, cfg.n_windows, cfg.ip_bins
        )
        donate = jax.default_backend() != "cpu"
        self._update = _jitted_update(cfg.backend, donate)
        self._snap = _jitted_snapshot(cfg.top_k, cfg.backend)
        self._sketch_state = (
            init_sketch(cfg.sketch_config) if cfg.sketch_enabled else None
        )
        self._sketch_update = (
            _jitted_sketch_update(cfg.backend, donate)
            if cfg.sketch_enabled else None
        )
        self._algo = None  # jitted lazily: most streams never ask for it
        self.n_ingested = 0
        self.health = IngestHealth()

    # -- state access --------------------------------------------------------
    @property
    def state(self) -> StreamState:
        return self._state

    @property
    def sketch_state(self) -> Optional[SketchState]:
        return self._sketch_state

    def block(self) -> StreamState:
        jax.block_until_ready(self._state)
        if self._sketch_state is not None:
            jax.block_until_ready(self._sketch_state)
        return self._state

    def merge_from(
        self, other: StreamState, sketch: Optional[SketchState] = None
    ) -> None:
        """Fold another shard's state into this engine (host-level merge).
        Pass the shard's ``sketch_state`` too when the sketch tier is on."""
        if self.cfg.exact_enabled:
            self._state = merge_states(self._state, other)
        if sketch is not None:
            if self._sketch_state is None:
                raise ValueError("sketch merge on a tier='exact' engine")
            self._sketch_state = merge_sketches(self._sketch_state, sketch)

    def load(
        self,
        state: Optional[StreamState] = None,
        sketch_state: Optional[SketchState] = None,
        health: Optional[IngestHealth] = None,
    ) -> None:
        """Adopt restored state (stream/recovery.py checkpoint restore).

        Leaves are re-placed with ``jax.device_put`` so every buffer is a
        fresh distinct device allocation — the donation contract
        (state.py) forbids aliased leaves, and restored numpy arrays may
        share memory with checkpoint read buffers.
        """
        if state is not None:
            self._state = jax.tree_util.tree_map(jax.device_put, state)
        if sketch_state is not None:
            if not self.cfg.sketch_enabled:
                raise ValueError("sketch state loaded into a tier='exact' engine")
            self._sketch_state = jax.tree_util.tree_map(
                jax.device_put, sketch_state
            )
        if health is not None:
            self.health = health

    # -- graceful degradation ------------------------------------------------
    def degrade(self, to_tier: str) -> None:
        """Switch the active tier forward (exact -> both -> sketch) under
        capacity pressure — DESIGN.md §2.7.

        Forward-only: re-enabling the exact tier after its state froze
        would silently un-count everything streamed in between.  When the
        switch turns the sketch tier on for the first time, the fresh
        sketch is *backfilled* from the exact link table — one weighted
        ``update_sketch`` over the accumulated ``(src, dst, packets)``
        rows — so its answers cover the full history, not just the tail
        (the CSR rows live in the original-IP domain, same as the sketch's
        input).  ``"sketch"`` freezes the exact state where it stands; its
        final answers stay queryable but stop advancing.  The switch is
        recorded in ``health.degraded_to``/``degraded_at_batch`` and
        surfaced on every subsequent snapshot — never silent.
        """
        if to_tier not in _TIER_ORDER:
            raise ValueError(f"unknown tier {to_tier!r}")
        if _TIER_ORDER[to_tier] <= _TIER_ORDER[self.cfg.tier]:
            raise ValueError(
                f"degrade is forward-only: {self.cfg.tier!r} -> {to_tier!r}"
            )
        at_batch = int(self._state.n_batches) if self.cfg.exact_enabled \
            else int(self._sketch_state.n_batches)
        if self._sketch_state is None:
            st = self._state
            self._sketch_state = update_sketch(
                init_sketch(self.cfg.sketch_config),
                st.src, st.dst, st.n_links,
                weights=st.packets, backend=self.cfg.backend,
            )
            self._sketch_update = _jitted_sketch_update(
                self.cfg.backend, jax.default_backend() != "cpu"
            )
        self.cfg = dataclasses.replace(self.cfg, tier=to_tier)
        self.health.degraded_to = to_tier
        self.health.degraded_at_batch = at_batch
        reg = get_registry()
        reg.counter("stream_degrade_total", "tier degradations applied").inc()
        reg.gauge("stream_tier",
                  "active tier (0=exact 1=both 2=sketch)"
                  ).set(_TIER_ORDER[to_tier])

    # -- ingest --------------------------------------------------------------
    def ingest(self, src, dst, win, n_valid: Optional[int] = None) -> None:
        """Fold one micro-batch; arrays may be shorter than batch_capacity."""
        cap = self.cfg.batch_capacity
        n = len(src) if n_valid is None else int(n_valid)
        if n > cap:
            raise ValueError(f"micro-batch of {n} rows exceeds "
                             f"batch_capacity {cap}")
        pad = lambda a: np.concatenate(
            [np.asarray(a[:n], np.int32), np.zeros(cap - n, np.int32)]
        )
        self.ingest_padded(pad(src), pad(dst), pad(win), n)

    def ingest_padded(self, src, dst, win, n_valid: int) -> None:
        """Fold a pre-padded (possibly already device-resident) micro-batch
        into every enabled tier."""
        if self.cfg.exact_enabled:
            self._state = self._update(self._state, src, dst, win, n_valid)
        if self.cfg.sketch_enabled:
            self._sketch_state = self._sketch_update(
                self._sketch_state, src, dst, n_valid
            )
        self.n_ingested += 1
        reg = get_registry()
        reg.counter("stream_batches_ingested_total",
                    "micro-batches folded into the stream state").inc()
        reg.counter("stream_packets_ingested_total",
                    "live packet rows folded").inc(int(n_valid))

    # -- queries -------------------------------------------------------------
    def snapshot(self, distributed: bool = False) -> StreamSnapshot:
        """Answer all challenge queries from the accumulated state.

        ``distributed=True`` merges the state's link table through the
        ``repro.dist`` shard_map path over all local devices (scalar suite
        only; raises on exchange overflow per the repo contract).
        """
        t0 = time.perf_counter()
        with obs_span("snapshot", tier=self.cfg.tier):
            state = self._state
            results = None
            if self.cfg.exact_enabled:
                with obs_span("exact"):
                    results = self._snap(state)
                    if distributed:
                        results = dataclasses.replace(
                            results,
                            scalars=distributed_scalar_queries(
                                link_table(state)),
                        )
                    jax.block_until_ready(results)
            sketch = None
            if self._sketch_state is not None:
                with obs_span("sketch"):
                    sketch = snapshot_sketch(self._sketch_state,
                                             k=self.cfg.top_k)
            exact = self.cfg.exact_enabled
            n_packets = int(state.n_packets) if exact \
                else int(self._sketch_state.n_packets)
            n_batches = int(state.n_batches) if exact \
                else int(self._sketch_state.n_batches)
            snap = StreamSnapshot(
                results=results,
                n_packets=n_packets,
                n_batches=n_batches,
                n_links=int(state.n_links) if exact else None,
                n_ips=int(state.n_ips) if exact else None,
                overflow=int(state.overflow) if exact else None,
                sketch=sketch,
                tier=self.cfg.tier,
                health=dataclasses.replace(self.health),
            )
        # snapshot time is the one spot that already forces a device sync,
        # so mirroring engine + ingest-health facts into the registry here
        # costs no extra block_until_ready on the hot ingest path
        reg = get_registry()
        reg.histogram("stream_snapshot_seconds",
                      "wall seconds per snapshot() query pass"
                      ).observe(time.perf_counter() - t0)
        reg.gauge("stream_packets", "packets folded so far").set(n_packets)
        reg.gauge("stream_batches", "batches folded so far").set(n_batches)
        if exact:
            reg.gauge("stream_links", "distinct links held").set(snap.n_links)
            reg.gauge("stream_ips", "dictionary entries held").set(snap.n_ips)
            reg.gauge("stream_overflow",
                      "rows dropped past capacity (0 == exact)"
                      ).set(snap.overflow)
        reg.gauge("stream_reliable",
                  "1 iff no overflow and no lost batches"
                  ).set(int(snap.reliable))
        h = self.health
        reg.gauge("ingest_duplicates_dropped", "").set(h.duplicates_dropped)
        reg.gauge("ingest_reordered_buffered", "").set(h.reordered_buffered)
        reg.gauge("ingest_quarantined", "").set(h.quarantined)
        reg.gauge("ingest_io_retries", "").set(h.io_retries)
        reg.gauge("ingest_lost_batches", "").set(h.lost_batches)
        reg.gauge("ingest_batches_replayed", "").set(h.batches_replayed)
        reg.gauge("ingest_crashes_recovered", "").set(h.crashes_recovered)
        reg.gauge("ingest_checkpoints_committed", "").set(h.checkpoints_committed)
        return snap

    def algorithms(self, source: int = 0):
        """BFS/CC/PageRank/triangles over everything streamed so far.

        Answers from the accumulated link-table CSR (two sorts over
        ``link_capacity`` rows, never the packet stream); equals the batch
        ``analyze(algorithms=True)`` pass on the concatenated stream up to
        id relabeling.  Returns an AlgorithmResults pytree (host-synced).
        """
        from .algorithms import snapshot_algorithms

        if self._algo is None:
            self._algo = jax.jit(
                functools.partial(snapshot_algorithms, backend=self.cfg.backend)
            )
        out = self._algo(self._state, jnp.asarray(source, jnp.int32))
        jax.block_until_ready(out)
        return out


# ---------------------------------------------------------------------------
# plq streaming driver (shared by repro.stream.run and repro.launch.serve)
# ---------------------------------------------------------------------------

def stream_plq(
    engine: StreamEngine,
    path: str,
    win_full: np.ndarray,
    *,
    columns: Sequence[str] = ("src", "dst"),
    depth: int = 2,
    time_phases: bool = False,
    on_batch: Optional[Callable[[int, StreamEngine], None]] = None,
) -> List[StreamBatchTimings]:
    """Stream a plq capture's row groups through the engine.

    Row groups are prefetched by a background thread (``Prefetcher``) while
    the device runs the previous update, and ``jax.device_put`` starts the
    next host->device copy before the current state is blocked on — the
    double-buffered service loop.  ``win_full`` holds precomputed window ids
    for every capture row (chunks arrive in file order).

    ``time_phases=True`` blocks after transfer and update to attribute wall
    time per phase (accurate phases, no overlap); the default overlapped
    mode records dispatch walls only and is the throughput measurement —
    see docs/METHODOLOGY.md.  The whole pass, up to its final
    ``engine.block()``, is the ``stream.pass`` span.
    """
    cap = engine.cfg.batch_capacity
    timings: List[StreamBatchTimings] = []
    off = 0
    with obs_span("stream.pass", tier=engine.cfg.tier):
        for i, chunk in enumerate(Prefetcher(
                read_plq_chunks(path, list(columns)), depth=depth)):
            t_start = time.perf_counter()
            compiles = jit_compile_count()
            n = len(chunk[columns[0]])
            if n > cap:
                raise ValueError(
                    f"row group {i} has {n} rows > batch_capacity {cap}; "
                    f"rewrite the capture with row_group_size <= {cap}"
                )
            pad = lambda a: np.concatenate(
                [np.asarray(a, np.int32), np.zeros(cap - len(a), np.int32)]
            )
            src = pad(chunk["src"])
            dst = pad(chunk["dst"])
            win = pad(win_full[off:off + n])
            off += n
            t1 = time.perf_counter()
            dev_src, dev_dst, dev_win = jax.device_put((src, dst, win))
            if time_phases:
                jax.block_until_ready((dev_src, dev_dst, dev_win))
            t2 = time.perf_counter()
            engine.ingest_padded(dev_src, dev_dst, dev_win, n)
            if time_phases:
                engine.block()
            t3 = time.perf_counter()
            # a batch is a compile batch iff JAX compiled (or loaded from the
            # persistent cache) a program while it ran
            timings.append(StreamBatchTimings(
                n_packets=n, prep_s=t1 - t_start, transfer_s=t2 - t1,
                update_s=t3 - t2, total_s=t3 - t_start,
                compile=jit_compile_count() > compiles,
            ))
            if on_batch is not None:
                on_batch(i, engine)
        engine.block()
    return timings
