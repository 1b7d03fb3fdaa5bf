"""Sketch-based bounded-memory analytics tier (DESIGN.md §2.6).

The exact CSR substrate answers every challenge query bit-exactly — until
a static capacity fills, after which overflow is *counted* but the dropped
traffic is still lost (``stream/state.py``).  This module is the
approximate tier beside it: three classical mergeable summaries whose
memory is **fixed at configuration time and independent of traffic
volume**, with machine-checked error bounds instead of exactness:

  * **Count–Min sketch** (conservative-update variant) — per-link
    ``(src, dst)`` and per-source packet counts.  ``depth × width`` cells;
    a point estimate **never underestimates** and overestimates by more
    than ``e/width · N`` with probability at most ``e^-depth``
    (Cormode & Muthukrishnan; the CU variant is cell-wise dominated by
    the classic sketch, so the classic bound still holds — and CU states
    merge by plain addition without breaking the lower-bound invariant,
    since ``min_r(a_r + b_r) >= min_r a_r + min_r b_r``).
  * **HyperLogLog** — unique sources / destinations / links.  ``2^p``
    registers; relative error concentrates around ``1.04 / sqrt(2^p)``
    (Flajolet et al.), with the linear-counting small-range correction.
    Registers merge by element-wise max.
  * **Space-saving heavy hitters** — top-k talkers and links.  Stored in
    the Misra–Gries normal form (counts lower-bound the truth) plus the
    accumulated decrement ``offset``; the space-saving estimate
    ``count + offset`` never underestimates, errs by at most ``offset``,
    and ``offset <= N / (capacity + 1)`` — so every key with true count
    above ``N/(capacity+1)`` is **guaranteed present** (the superset
    guarantee the detection queries rely on).

All three live in one :class:`SketchState` pytree with
``update_sketch`` / ``merge_sketches`` / ``snapshot_sketch`` mirroring the
``StreamState`` semantics, so ``stream/engine.py`` can run ``exact``,
``sketch`` or ``both`` tiers per micro-batch.  CMS and HLL merges are
associative and commutative **bit-identically**; the heavy-hitter merge is
commutative bit-identically and associative up to its error bound (the
decrement schedule depends on grouping — property-tested in
tests/test_sketch_properties.py).

Updates ride the repo's kernel vocabulary: the CMS fold is one
``kernels.ops.cms_update`` dispatch (Pallas scatter-max grid), the HLL
fold is the segmented-max accumulate path, and the heavy-hitter fold is
one group-by + top-k — all static-shape, jittable, donation-friendly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import cms_update, segmented_reduce
from ..obs import span as obs_span
from .ops import groupby_aggregate, mix32, top_k

__all__ = [
    "SketchConfig",
    "SketchState",
    "SketchSnapshot",
    "init_sketch",
    "update_sketch",
    "merge_sketches",
    "snapshot_sketch",
    "sketch_scalars",
    "estimate_link_packets",
    "estimate_source_packets",
    "hll_cardinality",
    "heavy_links",
    "heavy_talkers",
    "error_bounds",
]

_I32_MAX = jnp.iinfo(jnp.int32).max
_GOLD = 0x9E3779B9       # 32-bit golden-ratio constant (salt mixing)
_ROW_SALT = 0x85EBCA6B   # per-depth-row salt stride (odd, from murmur3)


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SketchConfig:
    """Static geometry of one sketch tier.

    Memory is fixed by these at configuration time: the CMS holds
    ``2 · cms_depth · cms_width`` int32 cells (exact counts up to 2^31-1,
    the ``n_packets`` counter's own ceiling), HLL ``3 · 2^hll_p``
    float32 registers, and the heavy-hitter tables ``O(heavy_capacity)``
    int32 entries — independent of how much traffic is folded in.  The
    error bounds they imply (see :func:`error_bounds`):

      * CMS: estimates never underestimate; overestimate beyond
        ``(e / cms_width) · N`` with probability <= ``e^-cms_depth``.
      * HLL: relative cardinality error within
        ``hll_sigma · 1.04 / sqrt(2^hll_p)``.
      * heavy hitters: estimate error <= ``N / (heavy_capacity + 1)``;
        any key heavier than that is guaranteed present.
    """

    cms_depth: int = 4
    cms_width: int = 4096
    hll_p: int = 12              # 2^p registers per cardinality
    heavy_capacity: int = 64     # space-saving counters per summary
    seed: int = 0                # hash-family salt
    hll_sigma: float = 4.0       # HLL bound = sigma standard errors

    def __post_init__(self):
        if self.cms_depth < 1:
            raise ValueError("cms_depth must be >= 1")
        if self.cms_width < 2:
            raise ValueError("cms_width must be >= 2")
        if not 4 <= self.hll_p <= 18:
            raise ValueError("hll_p must be in [4, 18]")
        if self.heavy_capacity < 1:
            raise ValueError("heavy_capacity must be >= 1")
        if self.hll_sigma <= 0:
            raise ValueError("hll_sigma must be > 0")

    @property
    def hll_m(self) -> int:
        return 1 << self.hll_p

    @property
    def cms_epsilon(self) -> float:
        return math.e / self.cms_width

    @property
    def cms_delta(self) -> float:
        return math.exp(-self.cms_depth)

    @property
    def hll_rel_tolerance(self) -> float:
        return self.hll_sigma * 1.04 / math.sqrt(self.hll_m)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SketchState:
    """One shard's accumulated sketch tier (a pytree; ``seed`` is static).

    Heavy-hitter tables are stored in descending-count order with ties
    toward the lexicographically smallest key; empty slots hold key
    ``int32 max`` and count 0.
    """

    # Count–Min (conservative update): per-link and per-source packets.
    # int32 cells: counts stay exact up to 2^31-1 (the same ceiling as the
    # n_packets counter) — float32 would silently round past 2^24 and
    # break the never-underestimate guarantee.
    cms_links: jnp.ndarray       # (depth, width) int32
    cms_sources: jnp.ndarray     # (depth, width) int32
    # HyperLogLog registers
    hll_src: jnp.ndarray         # (m,) float32
    hll_dst: jnp.ndarray         # (m,) float32
    hll_links: jnp.ndarray       # (m,) float32
    # space-saving heavy hitters (Misra–Gries normal form + offset)
    hh_link_src: jnp.ndarray     # (heavy_capacity,) int32, pad = int32 max
    hh_link_dst: jnp.ndarray     # (heavy_capacity,) int32
    hh_link_count: jnp.ndarray   # (heavy_capacity,) int32, pad = 0
    hh_link_offset: jnp.ndarray  # scalar int32 — total decremented mass
    hh_src_key: jnp.ndarray      # (heavy_capacity,) int32
    hh_src_count: jnp.ndarray    # (heavy_capacity,) int32
    hh_src_offset: jnp.ndarray   # scalar int32
    # totals
    n_packets: jnp.ndarray       # scalar int32
    n_batches: jnp.ndarray       # scalar int32
    # static: hash-family salt (part of the merge compatibility contract)
    seed: int

    @property
    def cms_depth(self) -> int:
        return self.cms_links.shape[0]

    @property
    def cms_width(self) -> int:
        return self.cms_links.shape[1]

    @property
    def hll_m(self) -> int:
        return self.hll_src.shape[0]

    @property
    def hll_p(self) -> int:
        return int(self.hll_m).bit_length() - 1

    @property
    def heavy_capacity(self) -> int:
        return self.hh_link_count.shape[0]


jax.tree_util.register_dataclass(
    SketchState,
    data_fields=[
        f.name for f in dataclasses.fields(SketchState) if f.name != "seed"
    ],
    meta_fields=["seed"],
)


def init_sketch(cfg: SketchConfig) -> SketchState:
    """The empty (identity) state: ``merge(init, s) == s`` for any ``s``.

    Every leaf is a freshly allocated buffer — no two pytree leaves may
    alias, because ``StreamEngine`` jits ``update_sketch`` with
    ``donate_argnums=(0,)`` off-CPU and XLA rejects donating the same
    buffer twice (tests/test_sketch_properties.py locks the invariant).
    """
    def cms():
        return jnp.zeros((cfg.cms_depth, cfg.cms_width), jnp.int32)

    def regs():
        return jnp.zeros((cfg.hll_m,), jnp.float32)

    def zero():
        return jnp.zeros((), jnp.int32)

    k = cfg.heavy_capacity
    return SketchState(
        cms_links=cms(), cms_sources=cms(),
        hll_src=regs(), hll_dst=regs(), hll_links=regs(),
        hh_link_src=jnp.full((k,), _I32_MAX, jnp.int32),
        hh_link_dst=jnp.full((k,), _I32_MAX, jnp.int32),
        hh_link_count=jnp.zeros((k,), jnp.int32),
        hh_link_offset=zero(),
        hh_src_key=jnp.full((k,), _I32_MAX, jnp.int32),
        hh_src_count=jnp.zeros((k,), jnp.int32),
        hh_src_offset=zero(),
        n_packets=zero(), n_batches=zero(),
        seed=cfg.seed,
    )


# ---------------------------------------------------------------------------
# hashing (one mix32 family, salted per structure and per depth row)
# ---------------------------------------------------------------------------

def _hash_src(src: jnp.ndarray, salt: int) -> jnp.ndarray:
    """uint32 hash of a single key under ``salt``."""
    return mix32(src.astype(jnp.uint32) + jnp.uint32(salt & 0xFFFFFFFF))


def _hash_link(src: jnp.ndarray, dst: jnp.ndarray, salt: int) -> jnp.ndarray:
    """uint32 hash of a key pair: mix each endpoint, then mix the xor."""
    hs = mix32(src.astype(jnp.uint32) + jnp.uint32(salt & 0xFFFFFFFF))
    hd = mix32(dst.astype(jnp.uint32) + jnp.uint32((salt ^ _GOLD) & 0xFFFFFFFF))
    return mix32(hs ^ hd)


def _cms_cols(
    hashes_per_row, width: int
) -> jnp.ndarray:
    """Stack per-row uint32 hashes into (depth, n) int32 column ids."""
    return jnp.stack(
        [(h % jnp.uint32(width)).astype(jnp.int32) for h in hashes_per_row]
    )


def _link_rows(src, dst, seed: int, depth: int, width: int) -> jnp.ndarray:
    return _cms_cols(
        [_hash_link(src, dst, seed + (r + 1) * _ROW_SALT) for r in range(depth)],
        width,
    )


def _src_rows(src, seed: int, depth: int, width: int) -> jnp.ndarray:
    return _cms_cols(
        [_hash_src(src, seed + (r + 1) * _ROW_SALT + _GOLD)
         for r in range(depth)],
        width,
    )


def _floor_log2_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Exact floor(log2(x)) for uint32 ``x > 0`` (integer binary reduce —
    no float round-trip, which mis-floors near powers of two)."""
    y = x.astype(jnp.uint32)
    n = jnp.zeros(x.shape, jnp.int32)
    for s in (16, 8, 4, 2, 1):
        big = y >= jnp.uint32(1 << s)
        n = n + jnp.where(big, s, 0)
        y = jnp.where(big, y >> s, y)
    return n


def _hll_parts(h: jnp.ndarray, p: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split a uint32 hash into (register id, rho).

    Register = top ``p`` bits; rho = 1 + leading zeros of the remaining
    ``32 - p`` bits, capped at ``32 - p + 1`` when the residual is zero.
    """
    reg = (h >> jnp.uint32(32 - p)).astype(jnp.int32)
    w = (h << jnp.uint32(p)).astype(jnp.uint32)  # residual in the top bits
    rho = jnp.where(
        w == 0,
        jnp.int32(32 - p + 1),
        jnp.int32(32) - _floor_log2_u32(jnp.maximum(w, 1)),
    )
    return reg, rho


# ---------------------------------------------------------------------------
# space-saving fold (Misra–Gries merge with decrement accounting)
# ---------------------------------------------------------------------------

def _ss_fold(
    keys_a, counts_a, offset_a,
    keys_b, counts_b, valid_b, offset_b,
    capacity: int,
):
    """Fold candidate (key, count) rows into a space-saving summary.

    One concat group-by sums coincident keys, then the classic Misra–Gries
    merge step: subtract the ``(capacity+1)``-th largest merged count from
    everything, keep the survivors (at most ``capacity``), and add the
    subtraction to ``offset``.  Each decrement removes >= ``capacity+1``
    times its value in mass, so ``offset <= N / (capacity + 1)`` — the
    space-saving guarantee.  The group-by canonicalises the union and
    ``top_k`` ties break toward the lexicographically smallest key, so the
    fold is a pure function of the (multiset) union: **commutative
    bit-identically**.  Returns (keys, counts, offset).
    """
    cat_keys = [jnp.concatenate([ka, kb]) for ka, kb in zip(keys_a, keys_b)]
    cat_counts = jnp.concatenate([counts_a, counts_b]).astype(jnp.int32)
    cat_valid = jnp.concatenate([counts_a > 0, valid_b])
    g = groupby_aggregate(
        cat_keys, {"count": (cat_counts, "sum")},
        valid_mask=cat_valid, count_name=None,
    )
    vals, idx, n_live = top_k(g.aggs["count"], capacity + 1, g.mask())
    thr = jnp.where(n_live > capacity, vals[capacity], 0).astype(jnp.int32)
    kept = vals[:capacity].astype(jnp.int32) - thr
    keep = (jnp.arange(capacity, dtype=jnp.int32) < n_live) & (kept > 0)
    out_counts = jnp.where(keep, kept, 0)
    out_keys = [
        jnp.where(keep, k[idx[:capacity]], _I32_MAX) for k in g.keys
    ]
    return out_keys, out_counts, offset_a + offset_b + thr


# ---------------------------------------------------------------------------
# the state transition (pure, jittable)
# ---------------------------------------------------------------------------

def update_sketch(
    state: SketchState,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    n_valid,
    *,
    weights: Optional[jnp.ndarray] = None,
    backend: str = "auto",
) -> SketchState:
    """Fold one micro-batch (padded to a static capacity) into the sketch.

    ``weights`` is the per-row packet multiplicity (1 per row when the
    batch is one-row-per-packet).  The batch is first collapsed to
    distinct links / sources (the conservative-update rule needs per-key
    batch totals so repeated keys inside one batch cannot undercount),
    then each summary folds in one dispatch.  Nothing overflows, ever —
    the sketches absorb arbitrary traffic at fixed memory; accuracy, not
    capacity, is what degrades.

    Device scopes: ``update_sketch/groupby``, ``/cms``, ``/hll``,
    ``/heavy``.
    """
    with jax.named_scope("update_sketch"):
        cap = src.shape[0]
        n_valid = jnp.asarray(n_valid, jnp.int32)
        src = src.astype(jnp.int32)
        dst = dst.astype(jnp.int32)
        valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
        w = (jnp.ones((cap,), jnp.int32) if weights is None
             else weights.astype(jnp.int32))
        w = jnp.where(valid, w, 0)
        seed, depth, width = state.seed, state.cms_depth, state.cms_width

        # batch group-bys: distinct links and distinct sources with totals
        with jax.named_scope("groupby"):
            g_link = groupby_aggregate(
                [src, dst], {"packets": (w, "sum")},
                valid_mask=valid, count_name=None,
            )
            g_src = groupby_aggregate(
                [src], {"packets": (w, "sum")},
                valid_mask=valid, count_name=None,
            )

        def cms_fold(counts, rows, group_counts, mask):
            # conservative update: propose est + batch_count at every row cell.
            # All int32 end to end — a float32 round-trip would round the
            # proposal down past 2^24 and underestimate.
            safe = jnp.clip(rows, 0, width - 1)
            gathered = jnp.stack(
                [counts[r][safe[r]] for r in range(depth)]
            )  # (depth, cap)
            est = jnp.min(gathered, axis=0)
            props = jnp.where(mask, est + group_counts.astype(jnp.int32), 0)
            ids = jnp.where(mask[None, :], rows, -1)
            return cms_update(counts, ids, props, backend=backend)

        lmask = g_link.mask() & (g_link.aggs["packets"] > 0)
        smask = g_src.mask() & (g_src.aggs["packets"] > 0)
        with jax.named_scope("cms"):
            cms_links = cms_fold(
                state.cms_links,
                _link_rows(g_link.keys[0], g_link.keys[1], seed, depth,
                           width),
                g_link.aggs["packets"], lmask,
            )
            cms_sources = cms_fold(
                state.cms_sources,
                _src_rows(g_src.keys[0], seed, depth, width),
                g_src.aggs["packets"], smask,
            )

        # HLL folds over raw rows (duplicates are harmless to a max fold)
        p = state.hll_p

        def hll_fold(regs, hashes, mask):
            reg, rho = _hll_parts(hashes, p)
            return segmented_reduce(
                rho.astype(jnp.float32), jnp.where(mask, reg, -1),
                state.hll_m, op="max", init=regs, backend=backend,
            )

        with jax.named_scope("hll"):
            hll_src = hll_fold(state.hll_src, _hash_src(src, seed + 1),
                               valid)
            hll_dst = hll_fold(state.hll_dst, _hash_src(dst, seed + 2),
                               valid)
            hll_links = hll_fold(state.hll_links,
                                 _hash_link(src, dst, seed + 3), valid)

        # space-saving folds over the batch-distinct groups
        with jax.named_scope("heavy"):
            (hl_src, hl_dst), hl_count, hl_off = _ss_fold(
                [state.hh_link_src, state.hh_link_dst], state.hh_link_count,
                state.hh_link_offset,
                [g_link.keys[0], g_link.keys[1]], g_link.aggs["packets"],
                lmask, jnp.zeros((), jnp.int32), state.heavy_capacity,
            )
            (hs_key,), hs_count, hs_off = _ss_fold(
                [state.hh_src_key], state.hh_src_count, state.hh_src_offset,
                [g_src.keys[0]], g_src.aggs["packets"], smask,
                jnp.zeros((), jnp.int32), state.heavy_capacity,
            )

        return SketchState(
            cms_links=cms_links, cms_sources=cms_sources,
            hll_src=hll_src, hll_dst=hll_dst, hll_links=hll_links,
            hh_link_src=hl_src, hh_link_dst=hl_dst, hh_link_count=hl_count,
            hh_link_offset=hl_off,
            hh_src_key=hs_key, hh_src_count=hs_count, hh_src_offset=hs_off,
            n_packets=state.n_packets + jnp.sum(w),
            n_batches=state.n_batches + 1,
            seed=seed,
        )


def merge_sketches(a: SketchState, b: SketchState) -> SketchState:
    """Merge two independently built sketch states (same geometry + seed).

    CMS merges by addition (the conservative-update lower-bound invariant
    survives: ``min_r(a+b) >= min_r a + min_r b``), HLL by element-wise
    max — both associative and commutative bit-identically.  Heavy-hitter
    tables merge through the Misra–Gries fold: commutative bit-identically,
    associative up to the error bound (offsets from different groupings
    may differ; the superset guarantee and ``count <= true <= count +
    offset`` hold for every grouping).
    """
    if (a.cms_links.shape != b.cms_links.shape
            or a.hll_m != b.hll_m
            or a.heavy_capacity != b.heavy_capacity
            or a.seed != b.seed):
        raise ValueError(
            "merge_sketches requires equal geometry and seed: "
            f"cms {a.cms_links.shape}/{b.cms_links.shape}, "
            f"hll {a.hll_m}/{b.hll_m}, "
            f"heavy {a.heavy_capacity}/{b.heavy_capacity}, "
            f"seed {a.seed}/{b.seed}"
        )
    (hl_src, hl_dst), hl_count, hl_off = _ss_fold(
        [a.hh_link_src, a.hh_link_dst], a.hh_link_count, a.hh_link_offset,
        [b.hh_link_src, b.hh_link_dst], b.hh_link_count, b.hh_link_count > 0,
        b.hh_link_offset, a.heavy_capacity,
    )
    (hs_key,), hs_count, hs_off = _ss_fold(
        [a.hh_src_key], a.hh_src_count, a.hh_src_offset,
        [b.hh_src_key], b.hh_src_count, b.hh_src_count > 0,
        b.hh_src_offset, a.heavy_capacity,
    )
    return SketchState(
        cms_links=a.cms_links + b.cms_links,
        cms_sources=a.cms_sources + b.cms_sources,
        hll_src=jnp.maximum(a.hll_src, b.hll_src),
        hll_dst=jnp.maximum(a.hll_dst, b.hll_dst),
        hll_links=jnp.maximum(a.hll_links, b.hll_links),
        hh_link_src=hl_src, hh_link_dst=hl_dst, hh_link_count=hl_count,
        hh_link_offset=hl_off,
        hh_src_key=hs_key, hh_src_count=hs_count, hh_src_offset=hs_off,
        n_packets=a.n_packets + b.n_packets,
        n_batches=a.n_batches + b.n_batches,
        seed=a.seed,
    )


# ---------------------------------------------------------------------------
# queries over the state
# ---------------------------------------------------------------------------

def estimate_link_packets(
    state: SketchState, src: jnp.ndarray, dst: jnp.ndarray
) -> jnp.ndarray:
    """CMS point estimate of per-link packet counts (never underestimates)."""
    rows = _link_rows(src.astype(jnp.int32), dst.astype(jnp.int32),
                      state.seed, state.cms_depth, state.cms_width)
    gathered = jnp.stack(
        [state.cms_links[r][rows[r]] for r in range(state.cms_depth)]
    )
    return jnp.min(gathered, axis=0)


def estimate_source_packets(
    state: SketchState, src: jnp.ndarray
) -> jnp.ndarray:
    """CMS point estimate of per-source packet counts (never underestimates)."""
    rows = _src_rows(src.astype(jnp.int32), state.seed,
                     state.cms_depth, state.cms_width)
    gathered = jnp.stack(
        [state.cms_sources[r][rows[r]] for r in range(state.cms_depth)]
    )
    return jnp.min(gathered, axis=0)


def hll_cardinality(registers: jnp.ndarray) -> jnp.ndarray:
    """HyperLogLog estimate with the linear-counting small-range correction.

    The large-range (hash saturation) correction is omitted: it binds only
    past ~2^32/30 distinct keys, far beyond the 32-bit IP domain here.
    """
    m = registers.shape[0]
    alpha = {16: 0.673, 32: 0.697, 64: 0.709}.get(
        m, 0.7213 / (1.0 + 1.079 / m)
    )
    raw = alpha * m * m / jnp.sum(jnp.exp2(-registers))
    v = jnp.sum((registers == 0).astype(jnp.int32))
    small = m * (
        jnp.log(jnp.float32(m)) - jnp.log(jnp.maximum(v, 1).astype(jnp.float32))
    )
    return jnp.where((raw <= 2.5 * m) & (v > 0), small, raw)


def heavy_links(
    state: SketchState,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Space-saving top links: ``(src, dst, estimate, n_live)``.

    Entries are in descending estimate order; ``estimate = count + offset``
    never underestimates and errs by at most ``offset``.
    """
    live = state.hh_link_count > 0
    est = jnp.where(live, state.hh_link_count + state.hh_link_offset, 0)
    return (state.hh_link_src, state.hh_link_dst, est,
            jnp.sum(live.astype(jnp.int32)))


def heavy_talkers(
    state: SketchState,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Space-saving top sources: ``(src, estimate, n_live)``."""
    live = state.hh_src_count > 0
    est = jnp.where(live, state.hh_src_count + state.hh_src_offset, 0)
    return state.hh_src_key, est, jnp.sum(live.astype(jnp.int32))


def sketch_scalars(state: SketchState) -> Dict[str, jnp.ndarray]:
    """The scalar query suite the sketch tier can answer, as estimates.

    ``valid_packets`` is exact (a counter); the cardinalities are HLL
    estimates.  The maxima take, per stored heavy-hitter key, the tighter
    of the space-saving estimate and the CMS estimate — both never
    underestimate that key, so their min doesn't either — then the max
    over all stored keys.  Two-sided bound (always):
    ``true_max - offset <= est <= true_max + εN`` (w.p. the CMS bound):
    above, because the witness key is over-estimated by at most εN; below,
    because the true max key is either stored (then its min-estimate
    >= true_max) or was evicted, which requires ``true_max <= offset``.
    Taking only the top *slot* would be wrong: the largest stored count
    can belong to a different key than the true max, whose CMS estimate
    bounds nothing about it.
    """
    hl_src, hl_dst, hl_est, hl_n = heavy_links(state)
    hs_key, hs_est, hs_n = heavy_talkers(state)
    link_bound = jnp.minimum(
        hl_est, estimate_link_packets(state, hl_src, hl_dst)
    )
    src_bound = jnp.minimum(
        hs_est, estimate_source_packets(state, hs_key)
    )
    live_l = state.hh_link_count > 0
    live_s = state.hh_src_count > 0
    top_link = jnp.max(jnp.where(live_l, link_bound, 0))
    top_src = jnp.max(jnp.where(live_s, src_bound, 0))
    return {
        "valid_packets": state.n_packets,
        "n_unique_sources": hll_cardinality(state.hll_src),
        "n_unique_destinations": hll_cardinality(state.hll_dst),
        "unique_links": hll_cardinality(state.hll_links),
        "max_link_packets": jnp.where(hl_n > 0, top_link, 0),
        "max_source_packets": jnp.where(hs_n > 0, top_src, 0),
    }


def error_bounds(
    state: SketchState, hll_sigma: float = 4.0
) -> Dict[str, float]:
    """The configured theoretical bounds at the current traffic volume.

    These are what tests and the BENCH_sketches CI gate check observed
    errors against; see the module docstring for the statements.
    """
    n = float(int(state.n_packets))
    return {
        "cms_epsilon_n": (math.e / state.cms_width) * n,
        "cms_delta": math.exp(-state.cms_depth),
        "hll_rel_tolerance": hll_sigma * 1.04 / math.sqrt(state.hll_m),
        "heavy_offset_bound": n / (state.heavy_capacity + 1),
        "heavy_link_offset": float(int(state.hh_link_offset)),
        "heavy_src_offset": float(int(state.hh_src_offset)),
    }


# ---------------------------------------------------------------------------
# snapshot (host-side summary, mirroring StreamSnapshot)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SketchSnapshot:
    """Point-in-time sketch-tier answers (host values).

    ``overflow`` is definitionally 0 — a sketch absorbs arbitrary traffic
    at fixed memory; the cost is the error bounds carried in ``bounds``.
    """

    n_packets: int
    n_batches: int
    unique_sources: float          # HLL estimates
    unique_destinations: float
    unique_links: float
    max_link_packets: float        # min(space-saving, CMS) upper bounds
    max_source_packets: float
    top_link_src: np.ndarray       # descending-estimate heavy hitters
    top_link_dst: np.ndarray
    top_link_packets: np.ndarray
    n_top_links: int
    top_talker_src: np.ndarray
    top_talker_packets: np.ndarray
    n_top_talkers: int
    bounds: Dict[str, float]
    overflow: int = 0

    @property
    def reliable(self) -> bool:
        """Sketch answers are always 'reliable within bounds' — the bounds
        in ``bounds`` are the contract, not a best-effort flag."""
        return True


def snapshot_sketch(
    state: SketchState, k: Optional[int] = None, hll_sigma: float = 4.0
) -> SketchSnapshot:
    """Answer the sketch-tier query suite from the accumulated state.

    Runs eagerly on the host; each part is a child span of the caller's
    (``scalars``, ``heavy_links``, ``heavy_talkers``, ``bounds``), ending
    when its answers are host values."""
    k = state.heavy_capacity if k is None else min(k, state.heavy_capacity)
    with obs_span("scalars"):
        scalars = {n: float(v) for n, v in sketch_scalars(state).items()
                   if n != "valid_packets"}
        n_packets = int(state.n_packets)
        n_batches = int(state.n_batches)
    with obs_span("heavy_links"):
        hl_src, hl_dst, hl_est, hl_n = heavy_links(state)
        top_link_src = np.asarray(hl_src)[:k]
        top_link_dst = np.asarray(hl_dst)[:k]
        top_link_packets = np.asarray(hl_est)[:k]
        n_top_links = min(int(hl_n), k)
    with obs_span("heavy_talkers"):
        hs_key, hs_est, hs_n = heavy_talkers(state)
        top_talker_src = np.asarray(hs_key)[:k]
        top_talker_packets = np.asarray(hs_est)[:k]
        n_top_talkers = min(int(hs_n), k)
    with obs_span("bounds"):
        bounds = error_bounds(state, hll_sigma=hll_sigma)
    return SketchSnapshot(
        n_packets=n_packets,
        n_batches=n_batches,
        unique_sources=scalars["n_unique_sources"],
        unique_destinations=scalars["n_unique_destinations"],
        unique_links=scalars["unique_links"],
        max_link_packets=scalars["max_link_packets"],
        max_source_packets=scalars["max_source_packets"],
        top_link_src=top_link_src,
        top_link_dst=top_link_dst,
        top_link_packets=top_link_packets,
        n_top_links=n_top_links,
        top_talker_src=top_talker_src,
        top_talker_packets=top_talker_packets,
        n_top_talkers=n_top_talkers,
        bounds=bounds,
    )
