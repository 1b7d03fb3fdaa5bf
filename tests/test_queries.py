"""End-to-end tests: challenge queries + anonymization vs the NumPy oracle."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    Table,
    anonymize,
    count_hlo_sorts,
    factorize,
    hash_permutation,
    random_permutation,
    run_all_queries,
    traffic_matrix,
)
from repro.core import queries as Q
from repro.core.ref import (
    ref_anonymize_check,
    ref_run_all_queries,
    ref_traffic_matrix,
)


def make_table(src, dst, w=None, extra_cap=17):
    n = len(src)
    cap = n + extra_cap
    pad = lambda x: np.concatenate([np.asarray(x), np.full(cap - n, 99999, np.int32)])
    cols = {"src": pad(src), "dst": pad(dst)}
    if w is not None:
        cols["n_packets"] = pad(w)
    return Table.from_dict(cols, n_valid=n)


edges = st.lists(
    st.tuples(st.integers(0, 30), st.integers(0, 30)), min_size=1, max_size=300
)


@given(edges)
@settings(max_examples=40, deadline=None)
def test_all_queries_match_oracle(pairs):
    src = np.array([p[0] for p in pairs], np.int32)
    dst = np.array([p[1] for p in pairs], np.int32)
    res = jax.jit(run_all_queries)(make_table(src, dst))
    ref = ref_run_all_queries(src, dst)
    for k, v in ref.items():
        assert int(getattr(res, k)) == v, k


@given(edges, st.lists(st.integers(1, 20), min_size=300, max_size=300))
@settings(max_examples=25, deadline=None)
def test_weighted_queries_match_oracle(pairs, weights):
    src = np.array([p[0] for p in pairs], np.int32)
    dst = np.array([p[1] for p in pairs], np.int32)
    w = np.array(weights[: len(pairs)], np.int32)
    res = jax.jit(run_all_queries)(make_table(src, dst, w))
    ref = ref_run_all_queries(src, dst, w)
    for k, v in ref.items():
        assert int(getattr(res, k)) == v, k


def test_traffic_matrix_edge_list():
    src = np.array([2, 1, 2, 2], np.int32)
    dst = np.array([7, 7, 7, 3], np.int32)
    g = traffic_matrix(make_table(src, dst))
    k = int(g.n_groups)
    rs, rd, rp = ref_traffic_matrix(src, dst)
    np.testing.assert_array_equal(np.asarray(g.keys[0])[:k], rs)
    np.testing.assert_array_equal(np.asarray(g.keys[1])[:k], rd)
    np.testing.assert_array_equal(np.asarray(g.aggs["packets"])[:k], rp)


def test_individual_query_functions():
    src = np.array([1, 1, 2, 3, 1], np.int32)
    dst = np.array([9, 9, 9, 8, 7], np.int32)
    t = make_table(src, dst)
    assert int(Q.valid_packets(t)) == 5
    assert int(Q.unique_links(t)) == 4
    assert int(Q.max_link_packets(t)) == 2
    assert int(Q.unique_sources(t).n_unique) == 3
    assert int(Q.unique_destinations(t).n_unique) == 3
    assert int(Q.unique_ips(t).n_unique) == 6
    assert int(Q.max_source_packets(t)) == 3
    assert int(Q.max_source_fanout(t)) == 2  # src 1 -> {9, 7}
    assert int(Q.max_destination_packets(t)) == 3
    assert int(Q.max_destination_fanin(t)) == 2  # dst 9 <- {1, 2}


@pytest.mark.parametrize("method,rounds", [("shuffle", 1), ("shuffle", 2), ("hash", 1), ("hash", 3)])
def test_anonymize_is_isomorphism(method, rounds):
    rng = np.random.default_rng(7)
    src = rng.integers(0, 40, 500).astype(np.int32)
    dst = rng.integers(20, 60, 500).astype(np.int32)
    t = make_table(src, dst)
    key = jax.random.key(5) if method == "shuffle" else None
    res = anonymize(t, key, method=method, rounds=rounds)
    n = 500
    a_src = np.asarray(res.table["src"])[:n]
    a_dst = np.asarray(res.table["dst"])[:n]
    assert ref_anonymize_check(src, dst, a_src, a_dst)


def _ref_permutation(method, rounds, key, cap, n):
    """The permutation step alone, as anonymize composes its rounds."""
    if method == "shuffle":
        keys = jax.random.split(key, rounds)
        perm = random_permutation(keys[0], cap, n)
        for k in keys[1:]:
            perm = perm[random_permutation(k, cap, n)]
    else:
        perm = hash_permutation(cap, n)
        for r in range(1, rounds):
            perm = perm[hash_permutation(cap, n, salt=0x9E3779B9 + r)]
    return perm


@pytest.mark.parametrize("method", ["shuffle", "hash"])
@pytest.mark.parametrize("rounds", [1, 2])
def test_anonymize_equals_unique_factorize_gather(method, rounds):
    """anonymize == unique_ips, a binary search of each row over the IP
    domain, and a gather through the permutation, field by field on the
    live rows; its program holds one sort for unique and one per
    permutation round, and no loop beyond the permutation's own."""
    rng = np.random.default_rng(23)
    n = 300
    src = rng.integers(0, 90, n).astype(np.int32)
    dst = np.where(rng.random(n) < 0.2, src, rng.integers(50, 140, n))
    dst = dst.astype(np.int32)
    src[:3] = np.iinfo(np.int32).max
    t = make_table(src, dst)
    key = jax.random.key(9)
    fn = jax.jit(lambda t, k: anonymize(t, k, method=method, rounds=rounds))
    res = fn(t, key)

    ips = Q.unique_ips(t)
    cap = ips.values.shape[0]
    perm = _ref_permutation(method, rounds, key, cap, ips.n_unique)
    assert int(res.n_ips) == int(ips.n_unique)
    np.testing.assert_array_equal(np.asarray(res.ip_values),
                                  np.asarray(ips.values))
    np.testing.assert_array_equal(np.asarray(res.new_ids), np.asarray(perm))
    for col in ("src", "dst"):
        want = perm[factorize(t[col], ips.values)]
        np.testing.assert_array_equal(np.asarray(res.table[col])[:n],
                                      np.asarray(want)[:n])
    assert int(res.table.n_valid) == n

    hlo = fn.lower(t, key).compile().as_text()
    assert count_hlo_sorts(hlo) == 1 + rounds
    perm_hlo = jax.jit(lambda k, m: _ref_permutation(
        method, rounds, k, cap, m)).lower(key, ips.n_unique).compile().as_text()
    # a loop's tuple shape may hold "/*index=5*/": match the op name alone
    loops = lambda text: len(re.findall(r"\swhile\(", text))
    n_loops, n_perm_loops = loops(hlo), loops(perm_hlo)
    assert n_loops <= n_perm_loops


def test_anonymize_preserves_query_results():
    """Challenge invariant: every Table III statistic is anonymization-invariant."""
    rng = np.random.default_rng(11)
    src = rng.integers(0, 64, 800).astype(np.int32)
    dst = rng.integers(0, 64, 800).astype(np.int32)
    t = make_table(src, dst)
    res0 = jax.jit(run_all_queries)(t)
    anon = anonymize(t, jax.random.key(0))
    res1 = jax.jit(run_all_queries)(anon.table)
    for k, v in res0.as_dict().items():
        assert int(getattr(res1, k)) == int(v), k


def test_anonymize_shuffle_actually_moves_ids():
    src = np.arange(100, dtype=np.int32)
    dst = np.arange(100, 200, dtype=np.int32)
    t = make_table(src, dst)
    res = anonymize(t, jax.random.key(3))
    assert (np.asarray(res.table["src"])[:100] != src).any()
