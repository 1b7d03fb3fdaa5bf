"""Sort-once query planning tests (DESIGN.md §2.3).

Covers the packed-key sort edge cases (dtype extremes, empty/full validity,
payload stability), the SortedEdges derivations against the naive group-bys
(bit-identical buffers), the sort-free top-k, the lowered-HLO sort budget of
``analyze`` (<= 3 full-capacity sorts), and every query family of the full
challenge analysis against the NumPy oracles at scales 10 and 14.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    Table,
    argmax_top_k,
    count_hlo_sorts,
    groupby_aggregate,
    mix32,
    multi_key_sort,
    packable_keys,
    run_all_queries,
    top_k,
    top_links,
    top_links_from_plan,
    traffic_matrix,
    unique,
)
from repro.core.plan import (
    lead_fanout,
    lead_groups,
    link_groups,
    sorted_edges,
    unique_concat,
    unique_lead,
)
from repro.core.ref import (
    ref_run_all_queries,
    ref_top_links,
    ref_traffic_matrix,
    ref_window_ip_overlap,
    ref_windowed_histogram,
)

jax.config.update("jax_platform_name", "cpu")

I32_MAX = np.iinfo(np.int32).max
I32_MIN = np.iinfo(np.int32).min


# ----------------------------------------------------------- packed-key sort

def _ref_sorted(k0, k1, pay):
    """np.lexsort reference (stable) over the live prefix."""
    order = np.lexsort((pay, k1, k0))  # pay is already unique per row
    return k0[order], k1[order], pay[order]


@given(
    st.lists(st.integers(I32_MIN, I32_MAX), min_size=0, max_size=120),
    st.integers(0, 16),
)
@settings(max_examples=40, deadline=None)
def test_packed_two_key_sort_matches_lexsort(vals, extra_cap):
    """Property: full-range int32 keys, prefix validity, payload stability."""
    n = len(vals)
    cap = n + extra_cap + 1
    rng = np.random.default_rng(n * 31 + extra_cap)
    k0 = np.array(vals + [0] * (cap - n), np.int32)
    # duplicate-heavy second key so stability is actually exercised
    k1 = rng.integers(-3, 3, cap).astype(np.int32)
    pay = np.arange(cap, dtype=np.int32)
    (s0, s1), (p,) = multi_key_sort(
        [jnp.asarray(k0), jnp.asarray(k1)], [jnp.asarray(pay)], n_valid=n
    )
    r0, r1, rp = _ref_sorted(k0[:n], k1[:n], pay[:n])
    np.testing.assert_array_equal(np.asarray(s0)[:n], r0)
    np.testing.assert_array_equal(np.asarray(s1)[:n], r1)
    # stability: np.lexsort is stable, so payload order must match exactly
    np.testing.assert_array_equal(np.asarray(p)[:n], rp)


def test_packed_sort_dtype_extremes_at_validity_boundary():
    """A valid (INT32_MAX, INT32_MAX) row collides with the packed invalid
    sentinel; prefix validity must still keep it inside the live prefix."""
    k0 = np.array([I32_MAX, 7, I32_MAX, 99, 99], np.int32)
    k1 = np.array([I32_MAX, I32_MIN, I32_MAX, 99, 99], np.int32)
    pay = np.arange(5, dtype=np.int32)
    (s0, s1), (p,) = multi_key_sort(
        [jnp.asarray(k0), jnp.asarray(k1)], [jnp.asarray(pay)], n_valid=3
    )
    np.testing.assert_array_equal(np.asarray(p)[:3], [1, 0, 2])
    np.testing.assert_array_equal(np.asarray(s0)[:3], [7, I32_MAX, I32_MAX])
    np.testing.assert_array_equal(np.asarray(s1)[:3], [I32_MIN, I32_MAX, I32_MAX])


def test_packed_sort_collision_under_arbitrary_mask():
    """valid_mask (non-prefix) + a valid all-dtype-max row exercises the
    post-sort stable-partition repair."""
    k0 = np.array([I32_MAX, 5, I32_MAX, I32_MAX, I32_MIN, 5], np.int32)
    k1 = np.array([I32_MAX, 2, I32_MAX, I32_MAX, I32_MIN, 2], np.int32)
    mask = np.array([0, 1, 1, 0, 1, 1], bool)  # invalid rows precede valid max
    pay = np.arange(6, dtype=np.int32)
    (s0, s1), (p,) = jax.jit(
        lambda a, b, c, d: multi_key_sort([a, b], [c], valid_mask=d)
    )(jnp.asarray(k0), jnp.asarray(k1), jnp.asarray(pay), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(p)[:4], [4, 1, 5, 2])
    np.testing.assert_array_equal(np.asarray(s0)[:4], [I32_MIN, 5, 5, I32_MAX])
    np.testing.assert_array_equal(np.asarray(s1)[:4], [I32_MIN, 2, 2, I32_MAX])


@pytest.mark.parametrize("n_valid", [0, 8])
def test_packed_sort_empty_and_full_validity(n_valid):
    k0 = np.array([3, 1, I32_MAX, I32_MIN, 2, 2, 0, 1], np.int32)
    k1 = np.array([0, 1, I32_MAX, I32_MIN, 5, 4, 0, 0], np.int32)
    (s0, s1), (p,) = multi_key_sort(
        [jnp.asarray(k0), jnp.asarray(k1)],
        [jnp.asarray(np.arange(8, dtype=np.int32))],
        n_valid=n_valid,
    )
    if n_valid == 0:
        return  # nothing to assert beyond "no crash": the prefix is empty
    r0, r1, rp = _ref_sorted(k0, k1, np.arange(8, dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(s0), r0)
    np.testing.assert_array_equal(np.asarray(s1), r1)
    np.testing.assert_array_equal(np.asarray(p), rp)


def test_packed_single_key_mask_is_exact_for_dtype_max():
    """1-key layout spends a word bit on validity — no sentinel collision."""
    k = np.array([I32_MAX, 2, I32_MAX, 5], np.int32)
    mask = np.array([1, 0, 1, 1], bool)
    (s,), (p,) = multi_key_sort(
        [jnp.asarray(k)], [jnp.asarray(np.arange(4, dtype=np.int32))],
        valid_mask=jnp.asarray(mask),
    )
    np.testing.assert_array_equal(np.asarray(s)[:3], [5, I32_MAX, I32_MAX])
    np.testing.assert_array_equal(np.asarray(p)[:3], [3, 0, 2])


def test_packable_keys_predicate():
    i32 = jnp.zeros(4, jnp.int32)
    assert packable_keys([i32]) and packable_keys([i32, i32])
    assert not packable_keys([i32, i32, i32])
    assert not packable_keys([jnp.zeros(4, jnp.int64 if jax.config.jax_enable_x64
                                        else jnp.int16)])
    assert packable_keys([jnp.zeros(4, jnp.uint32), i32])


def test_packed_sort_is_single_operand_sort():
    """The packed path must lower to ONE uint64-keyed sort op."""
    t = jnp.zeros(32, jnp.int32)
    txt = jax.jit(
        lambda a, b, p: multi_key_sort([a, b], [p], n_valid=7)
    ).lower(t, t, t).compile().as_text()
    sort_lines = [l for l in txt.splitlines() if re.search(r"=\s[^=]*\bsort\(", l)]
    assert len(sort_lines) == 1, sort_lines
    assert "u64[32]" in sort_lines[0], sort_lines[0]


# -------------------------------------------------------- plan derivations

def _rand_table(seed, n, cap, hi=25, weights=True):
    rng = np.random.default_rng(seed)
    pad = lambda a, f: np.concatenate([a, np.full(cap - n, f, np.int32)])
    cols = {
        "src": pad(rng.integers(0, hi, n).astype(np.int32), 7),
        "dst": pad(rng.integers(0, hi, n).astype(np.int32), 7),
    }
    if weights:
        cols["n_packets"] = pad(rng.integers(1, 6, n).astype(np.int32), 1)
    return Table.from_dict(cols, n_valid=n)


@pytest.mark.parametrize("n,cap", [(0, 8), (1, 8), (200, 233), (64, 64)])
def test_plan_derivations_match_naive_groupbys(n, cap):
    t = _rand_table(3 * n + cap, n, cap)
    w = t["n_packets"]
    plan = sorted_edges(t["src"], t["dst"], weights=w, n_valid=t.n_valid)

    def assert_group_equal(got, want):
        assert int(got.n_groups) == int(want.n_groups)
        for g, x in zip(got.keys, want.keys):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
        assert sorted(got.aggs) == sorted(want.aggs)
        for k in want.aggs:
            np.testing.assert_array_equal(
                np.asarray(got.aggs[k]), np.asarray(want.aggs[k]), err_msg=k)

    assert_group_equal(link_groups(plan), traffic_matrix(t))
    assert_group_equal(
        lead_groups(plan),
        groupby_aggregate([t["src"]], {"packets": (w, "sum")}, n_valid=t.n_valid),
    )
    naive_links = traffic_matrix(t)
    assert_group_equal(
        lead_fanout(plan),
        groupby_aggregate([naive_links.keys[0]], None,
                          n_valid=naive_links.n_groups),
    )
    ul_plan, ul_naive = unique_lead(plan), unique(t["src"], n_valid=t.n_valid)
    assert int(ul_plan.n_unique) == int(ul_naive.n_unique)
    np.testing.assert_array_equal(np.asarray(ul_plan.values),
                                  np.asarray(ul_naive.values))
    np.testing.assert_array_equal(np.asarray(ul_plan.counts),
                                  np.asarray(ul_naive.counts))


def test_unique_concat_matches_masked_concat_groupby():
    """The stream dictionary's candidate extraction: compacted concat sort
    == the pre-plan validity-masked concat group-by (keys + min positions)."""
    rng = np.random.default_rng(5)
    n, cap = 90, 101
    src = np.concatenate([rng.integers(0, 30, n).astype(np.int32),
                          np.full(cap - n, 9, np.int32)])
    dst = np.concatenate([rng.integers(0, 30, n).astype(np.int32),
                          np.full(cap - n, 9, np.int32)])
    rows = np.arange(cap, dtype=np.int32)
    pos = np.concatenate([2 * rows, 2 * rows + 1])
    valid = rows < n
    got = unique_concat(jnp.asarray(src), jnp.asarray(dst), n,
                        positions=jnp.asarray(pos), count_name=None)
    want = groupby_aggregate(
        [jnp.asarray(np.concatenate([src, dst]))],
        {"first_pos": (jnp.asarray(pos), "min")},
        valid_mask=jnp.asarray(np.concatenate([valid, valid])),
        count_name=None,
    )
    k = int(want.n_groups)
    assert int(got.n_groups) == k
    np.testing.assert_array_equal(np.asarray(got.keys[0]),
                                  np.asarray(want.keys[0]))
    np.testing.assert_array_equal(np.asarray(got.aggs["first_pos"])[:k],
                                  np.asarray(want.aggs["first_pos"])[:k])


# ------------------------------------------------------------ sort-free top-k

@given(
    st.lists(st.integers(0, 12), min_size=0, max_size=60),
    st.integers(1, 12),
)
@settings(max_examples=30, deadline=None)
def test_argmax_top_k_matches_top_k(vals, k):
    cap = len(vals) + 5
    v = np.array(vals + [100] * 5, np.int32)  # tail garbage above live values
    mask = np.arange(cap) < len(vals)
    a = argmax_top_k(jnp.asarray(v), k, jnp.asarray(mask))
    b = top_k(jnp.asarray(v), k, jnp.asarray(mask))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_top_links_from_plan_matches_top_links():
    t = _rand_table(17, 300, 321, hi=9)
    plan = sorted_edges(t["src"], t["dst"], weights=t["n_packets"],
                        n_valid=t.n_valid)
    a = top_links_from_plan(plan, 8)
    b = top_links(t, 8)
    for f in ("src", "dst", "packets", "n_valid"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


# --------------------------------------------------------- HLO sort budget

def test_analyze_hlo_sort_budget():
    """THE acceptance gate: jit-traced analyze performs <= 3 full-capacity
    sorts (the two plan sorts and the ``unique_ips`` concat sort)."""
    from repro.challenge.pipeline import analyze

    cap = 512
    t = Table.from_dict(
        {k: np.zeros(cap, np.int32) for k in ("src", "dst", "win")},
        n_valid=cap - 3,
    )
    f = jax.jit(lambda t: analyze(t, n_windows=4, ip_bins=32, k=5,
                                  backend="xla"))
    plan_sorts = count_hlo_sorts(f.lower(t).compile().as_text(), cap)
    assert plan_sorts <= 3, f"plan analyze lowered to {plan_sorts} sorts"


def test_run_all_queries_hlo_sort_budget():
    t = Table.from_dict({k: np.zeros(256, np.int32) for k in ("src", "dst")},
                        n_valid=200)
    f = jax.jit(run_all_queries)
    assert count_hlo_sorts(f.lower(t).compile().as_text()) <= 3


# ------------------------------------------------- analyze == oracle at scale

WINDOWED_KEYS = (
    "valid_packets", "unique_links", "max_link_packets", "n_unique_sources",
    "n_unique_destinations", "max_source_packets", "max_source_fanout",
    "max_destination_packets", "max_destination_fanin",
)


@pytest.fixture(scope="module", params=[10, 14])
def analyzed(request):
    """One jitted ``analyze`` per scale over the challenge's synthetic
    capture, with the live host columns the oracles read."""
    from repro.challenge.pipeline import (
        ChallengeConfig,
        analyze,
        build_columns,
        build_table,
    )
    from repro.data.rmat import synthetic_packets

    scale = request.param
    cfg = ChallengeConfig(scale=scale, n_windows=4, ip_bins=64, top_k=7)
    cols = synthetic_packets(cfg.packets, scale=scale, seed=3)
    src, dst, win, n = build_columns(cols, cfg)
    res = jax.jit(lambda t: analyze(
        t, n_windows=cfg.n_windows, ip_bins=cfg.ip_bins, k=cfg.top_k,
        backend="xla"))(build_table(src, dst, win, n))
    host = {k: v[:n].astype(np.int64) for k, v in
            (("src", src), ("dst", dst), ("win", win))}
    return cfg, host, jax.device_get(res)


def _live(arr, n):
    return np.asarray(arr)[:int(n)]


def _check_group(g, keys, aggs):
    """A tail-padded group result's live prefix against oracle arrays."""
    n = int(g.n_groups)
    assert n == len(keys[0])
    for got, want in zip(g.keys, keys):
        np.testing.assert_array_equal(_live(got, n), want)
    for name, want in aggs.items():
        np.testing.assert_array_equal(_live(g.aggs[name], n), want, name)


@pytest.mark.parametrize("family", ["scalars", "vectors", "topk", "windowed",
                                    "activity", "overlap"])
def test_analyze_matches_oracles_at_scale(analyzed, family):
    """Every output of ``analyze``, entry by entry, against the NumPy
    oracles of ``core/ref.py`` (packets are unweighted: one row each)."""
    cfg, h, res = analyzed
    s, d, w = h["src"], h["dst"], h["win"]
    if family == "scalars":
        for k, v in ref_run_all_queries(s, d).items():
            assert int(getattr(res.scalars, k)) == v, k
    elif family == "vectors":
        ls, ld, lp = ref_traffic_matrix(s, d)
        _check_group(res.links, (ls, ld), {"packets": lp, "count": lp})
        for g, ips in ((res.per_source, s), (res.per_destination, d)):
            keys, counts = np.unique(ips, return_counts=True)
            _check_group(g, (keys,), {"packets": counts, "count": counts})
        for g, ends in ((res.source_fanout, ls), (res.destination_fanin, ld)):
            keys, counts = np.unique(ends, return_counts=True)
            _check_group(g, (keys,), {"count": counts})
        for u, ips in ((res.unique_sources, s), (res.unique_destinations, d)):
            keys, counts = np.unique(ips, return_counts=True)
            assert int(u.n_unique) == len(keys)
            np.testing.assert_array_equal(_live(u.values, len(keys)), keys)
            np.testing.assert_array_equal(_live(u.counts, len(keys)), counts)
    elif family == "topk":
        ts, td, tp = ref_top_links(s, d, cfg.top_k)
        top = res.top
        assert int(top.n_valid) == len(tp)
        for got, want in ((top.src, ts), (top.dst, td), (top.packets, tp)):
            np.testing.assert_array_equal(_live(got, len(tp)), want)
    elif family == "windowed":
        for win in range(cfg.n_windows):
            sel = w == win
            ref = (ref_run_all_queries(s[sel], d[sel]) if sel.any()
                   else dict.fromkeys(WINDOWED_KEYS, 0))
            for k in WINDOWED_KEYS:
                assert int(res.windowed[k][win]) == ref[k], (k, win)
    elif family == "activity":
        bins = np.asarray(mix32(jnp.asarray(s.astype(np.int32)))).astype(
            np.int64) % cfg.ip_bins
        np.testing.assert_array_equal(
            np.asarray(res.window_activity),
            ref_windowed_histogram(w, bins, cfg.n_windows, cfg.ip_bins))
    else:
        np.testing.assert_array_equal(
            np.asarray(res.window_ip_overlap),
            ref_window_ip_overlap(s, d, w, cfg.n_windows))
