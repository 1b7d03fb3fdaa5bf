"""GraphBLAS-lite CSR tests (DESIGN.md §2.4).

Covers the zero-sort plan->CSR construction against scipy.sparse (the
GraphBLAS reference role), duplicate-collapsing from_coo with overflow
truncation, ewise_union merge identities, plus/max reductions, masked
mxv/vxm against the dense oracle (and the Pallas segmented-reduction kernel
in interpret mode), the CSR scalar-suite equality, and the streaming state
transition against a NumPy fold of the same batches.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
import scipy.sparse as sp
from _hypothesis_compat import given, settings, st

from repro.core import Table
from repro.core.plan import count_hlo_sorts, sorted_edges
from repro.core.ops import masked_max
from repro.core.queries import run_all_queries, table_csrs
from repro.core.sparse import (
    csr_from_plan,
    degrees,
    ewise_union,
    from_coo,
    mxv,
    reduce_cols,
    reduce_rows,
    vxm,
)
from repro.kernels.ops import segmented_reduce
from repro.kernels.ref import ref_segmented_reduce

jax.config.update("jax_platform_name", "cpu")

I32_MAX = np.iinfo(np.int32).max


def _random_coo(seed, n, cap, hi=30, vhi=5):
    rng = np.random.default_rng(seed)
    pad = lambda a, f: np.concatenate([a, np.full(cap - n, f, np.int32)])
    rows = pad(rng.integers(0, hi, n).astype(np.int32), 3)
    cols = pad(rng.integers(0, hi, n).astype(np.int32), 3)
    vals = pad(rng.integers(1, vhi, n).astype(np.int32), 1)
    return rows, cols, vals


def _scipy_csr(rows, cols, vals, n, hi):
    A = sp.coo_matrix((vals[:n], (rows[:n], cols[:n])), shape=(hi, hi)).tocsr()
    A.sum_duplicates()
    return A


def _assert_matches_scipy(csr, A):
    assert int(csr.nnz) == A.nnz
    n_rows = int(np.sum(np.diff(A.indptr) > 0))
    assert int(csr.n_rows) == n_rows
    coo = A.tocoo()
    er = np.asarray(csr.entry_rows())[: A.nnz]
    rk = np.asarray(csr.row_keys[0])
    got = list(zip(rk[er], np.asarray(csr.col_keys)[: A.nnz],
                   np.asarray(csr.vals)[: A.nnz]))
    want = list(zip(coo.row, coo.col, coo.data))
    assert got == want  # CSR entry order IS the lex (row, col) order
    # row-pointer prefix validity: every padding row is empty
    ip = np.asarray(csr.indptr)
    assert (ip[int(csr.n_rows):] == A.nnz).all()
    assert (np.diff(ip) >= 0).all()


# ------------------------------------------------------------ construction

@pytest.mark.parametrize("n,cap", [(0, 8), (1, 8), (200, 233), (64, 64)])
def test_csr_from_plan_matches_scipy(n, cap):
    rows, cols, vals = _random_coo(n * 7 + cap, n, cap)
    plan = sorted_edges(rows, cols, weights=vals, n_valid=n)
    csr = csr_from_plan(plan)
    _assert_matches_scipy(csr, _scipy_csr(rows, cols, vals, n, 30))


@pytest.mark.parametrize("n,cap", [(0, 8), (150, 177)])
def test_from_coo_matches_plan_construction(n, cap):
    rows, cols, vals = _random_coo(n + cap, n, cap)
    a = csr_from_plan(sorted_edges(rows, cols, weights=vals, n_valid=n))
    b, dropped = from_coo([rows], cols, vals, n_valid=n)
    assert int(dropped) == 0
    for f in ("indptr", "col_keys", "vals"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), f)
    np.testing.assert_array_equal(np.asarray(a.row_keys[0]),
                                  np.asarray(b.row_keys[0]))
    assert int(a.n_rows) == int(b.n_rows) and int(a.nnz) == int(b.nnz)


def test_from_coo_truncation_counts_dropped():
    """Overflowing nnz_capacity keeps the lex-smallest groups and counts
    the rest — reported, never silent."""
    rows, cols, vals = _random_coo(9, 180, 200)
    full, d0 = from_coo([rows], cols, vals, n_valid=180)
    assert int(d0) == 0
    keep = int(full.nnz) // 2
    small, dropped = from_coo([rows], cols, vals, n_valid=180,
                              nnz_capacity=keep)
    assert int(small.nnz) == keep
    assert int(dropped) == int(full.nnz) - keep
    np.testing.assert_array_equal(np.asarray(small.col_keys)[:keep],
                                  np.asarray(full.col_keys)[:keep])
    np.testing.assert_array_equal(np.asarray(small.vals)[:keep],
                                  np.asarray(full.vals)[:keep])
    # row structure consistent after the cut: pointers clipped to nnz
    er = np.asarray(small.entry_rows())[:keep]
    assert (np.diff(er) >= 0).all()
    assert int(small.n_rows) == er[-1] + 1
    ip = np.asarray(small.indptr)
    assert ip[int(small.n_rows)] == keep and (ip <= keep).all()


@pytest.mark.parametrize("op", ["plus", "max", "min"])
def test_from_coo_dup_collapse_ops(op):
    rows = np.array([2, 2, 2, 5, 5, 0], np.int32)
    cols = np.array([1, 1, 1, 3, 3, 9], np.int32)
    vals = np.array([4, 7, 2, 10, 3, 6], np.int32)
    csr, dropped = from_coo([rows], cols, vals, op=op)
    assert int(dropped) == 0 and int(csr.nnz) == 3
    want = {"plus": [6, 13, 13], "max": [6, 7, 10], "min": [6, 2, 3]}[op]
    np.testing.assert_array_equal(np.asarray(csr.vals)[:3], want)
    np.testing.assert_array_equal(np.asarray(csr.row_keys[0])[:3], [0, 2, 5])


# ------------------------------------------------------------- ewise_union

def test_ewise_union_is_sparse_add():
    ra, ca, va = _random_coo(1, 120, 140)
    rb, cb, vb = _random_coo(2, 90, 140)
    A = _scipy_csr(ra, ca, va, 120, 30)
    B = _scipy_csr(rb, cb, vb, 90, 30)
    ca_ = csr_from_plan(sorted_edges(ra, ca, weights=va, n_valid=120))
    cb_ = csr_from_plan(sorted_edges(rb, cb, weights=vb, n_valid=90))
    # default capacity (max of the operands) mimics the stream state's
    # fixed buffers and may truncate; give the union full headroom here
    u, dropped = ewise_union(ca_, cb_, nnz_capacity=280)
    assert int(dropped) == 0
    S = (A + B).tocsr()
    S.sum_duplicates()
    _assert_matches_scipy(u, S)


def test_ewise_union_empty_identity_and_commutativity():
    r, c, v = _random_coo(3, 100, 128)
    a = csr_from_plan(sorted_edges(r, c, weights=v, n_valid=100))
    empty, _ = from_coo([np.full(128, I32_MAX, np.int32)],
                        np.full(128, I32_MAX, np.int32),
                        np.zeros(128, np.int32), n_valid=0)
    for left, right in ((a, empty), (empty, a)):
        u, d = ewise_union(left, right)
        assert int(d) == 0
        for f in ("indptr", "col_keys", "vals"):
            np.testing.assert_array_equal(np.asarray(getattr(u, f)),
                                          np.asarray(getattr(a, f)), f)
        assert int(u.n_rows) == int(a.n_rows) and int(u.nnz) == int(a.nnz)


# -------------------------------------------------------------- reductions

def test_reductions_match_scipy():
    r, c, v = _random_coo(4, 300, 321, hi=25)
    A = _scipy_csr(r, c, v, 300, 25)
    csr = csr_from_plan(sorted_edges(r, c, weights=v, n_valid=300))
    live_rows = np.asarray(csr.row_keys[0])[: int(csr.n_rows)]
    rr = np.asarray(reduce_rows(csr, "plus"))[: int(csr.n_rows)]
    np.testing.assert_array_equal(
        rr, np.asarray(A.sum(axis=1)).ravel()[live_rows])
    rm = np.asarray(reduce_rows(csr, "max"))[: int(csr.n_rows)]
    np.testing.assert_array_equal(
        rm, np.asarray(A.max(axis=1).todense()).ravel()[live_rows])
    dg = np.asarray(degrees(csr))[: int(csr.n_rows)]
    np.testing.assert_array_equal(dg, np.diff(A.indptr)[live_rows])
    rc = np.asarray(reduce_cols(csr, 25, "plus"))
    np.testing.assert_array_equal(rc, np.asarray(A.sum(axis=0)).ravel())


# ------------------------------------------------------------- mxv / vxm

def test_mxv_vxm_match_dense_oracle():
    r, c, v = _random_coo(5, 400, 444, hi=40)
    A = _scipy_csr(r, c, v, 400, 40).toarray().astype(np.float64)
    csr = csr_from_plan(sorted_edges(r, c, weights=v, n_valid=400))
    n_rows = int(csr.n_rows)
    live = np.asarray(csr.row_keys[0])[:n_rows]
    rng = np.random.default_rng(0)
    x = rng.random(40).astype(np.float32)

    y = np.asarray(mxv(csr, x, backend="xla"))
    np.testing.assert_allclose(y[:n_rows], (A @ x)[live], rtol=1e-5)
    # max semiring: per-row max of A (mul="first" keeps the stored values)
    ym = np.asarray(mxv(csr, np.ones(40, np.float32), add="max",
                        mul="first", backend="xla"))
    np.testing.assert_allclose(ym[:n_rows], A.max(axis=1)[live])
    # structural mask zeroes unselected rows
    mask = np.zeros(csr.row_capacity, bool)
    mask[0] = True
    ymask = np.asarray(mxv(csr, x, mask=jnp.asarray(mask), backend="xla"))
    assert (ymask[1:] == 0).all() and ymask[0] == y[0]

    xr = rng.random(csr.row_capacity).astype(np.float32)
    yv = np.asarray(vxm(xr, csr, 40, backend="xla"))
    dense_x = np.zeros(40, np.float32)
    dense_x[live] = xr[:n_rows]
    np.testing.assert_allclose(yv, A.T @ dense_x, rtol=1e-4)


def test_segmented_reduce_empty_input():
    """n == 0 must yield the monoid identity (or the accumulator), not an
    uninitialized buffer — zero row blocks skip the Pallas kernel body."""
    vals = jnp.zeros((0,), jnp.float32)
    seg = jnp.zeros((0,), jnp.int32)
    init = jnp.asarray(np.arange(8, dtype=np.float32))
    for backend in ("xla", "interpret"):
        s = segmented_reduce(vals, seg, 8, op="sum", backend=backend)
        np.testing.assert_array_equal(np.asarray(s), 0.0)
        m = segmented_reduce(vals, seg, 8, op="max", backend=backend)
        assert np.all(np.asarray(m) == -np.inf)
        mi = segmented_reduce(vals, seg, 8, op="max", init=init,
                              backend=backend)
        np.testing.assert_array_equal(np.asarray(mi), np.asarray(init))


@given(st.integers(0, 5), st.integers(1, 500))
@settings(max_examples=15, deadline=None)
def test_segmented_reduce_interpret_matches_xla(seed, num_segments):
    rng = np.random.default_rng(seed)
    n = 700
    vals = (rng.random(n) * 9).astype(np.float32)
    seg = rng.integers(-1, num_segments + 2, n).astype(np.int32)
    init = (rng.random(num_segments) * 3).astype(np.float32)
    for op in ("sum", "max"):
        for i in (None, jnp.asarray(init)):
            a = segmented_reduce(jnp.asarray(vals), jnp.asarray(seg),
                                 num_segments, op=op, init=i, backend="xla")
            b = segmented_reduce(jnp.asarray(vals), jnp.asarray(seg),
                                 num_segments, op=op, init=i,
                                 backend="interpret")
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, err_msg=op)
            r = ref_segmented_reduce(jnp.asarray(vals), jnp.asarray(seg),
                                     num_segments, op, i)
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-5, err_msg=op)


# ------------------------------------------------- the CSR scalar suite

def test_run_all_queries_csr_bit_identical_and_sort_budget():
    """The ten scalars in matrix language over ``table_csrs`` — 1^T A 1,
    |A|_0, max(A), A·1, |A|_0·1 and the transpose mirrors — equal the
    group-by suite, and the CSR pair costs the two plan sorts alone."""
    rng = np.random.default_rng(7)
    n, cap = 3000, 3333
    pad = lambda a: np.concatenate([a, np.full(cap - n, 5, np.int32)])
    t = Table.from_dict({
        "src": pad(rng.integers(0, 200, n).astype(np.int32)),
        "dst": pad(rng.integers(0, 300, n).astype(np.int32)),
        "n_packets": pad(rng.integers(1, 6, n).astype(np.int32)),
    }, n_valid=n)
    a = jax.jit(run_all_queries)(t)
    csr_src, csr_dst = jax.jit(table_csrs)(t)
    live = csr_src.entry_mask()
    got = {
        "valid_packets": jnp.sum(jnp.where(live, csr_src.vals, 0)),
        "unique_links": csr_src.nnz,
        "max_link_packets": masked_max(csr_src.vals, live),
        "n_unique_sources": csr_src.n_rows,
        "n_unique_destinations": csr_dst.n_rows,
    }
    for side, csr in (("source", csr_src), ("destination", csr_dst)):
        rows = csr.row_mask()
        got[f"max_{side}_packets"] = masked_max(reduce_rows(csr, "plus"), rows)
        fan = "max_source_fanout" if side == "source" else "max_destination_fanin"
        got[fan] = masked_max(degrees(csr), rows)
    for k, v in got.items():
        assert int(v) == int(getattr(a, k)), k
    txt = jax.jit(table_csrs).lower(t).compile().as_text()
    assert count_hlo_sorts(txt) <= 2


# ------------------------------------- stream transition: CSR == NumPy fold

def test_stream_update_csr_bit_identical_to_naive():
    """The CSR link path (one from_coo upsert), batch by batch, against a
    NumPy fold of the same batches: the links as sorted (win, src, dst)
    with packet sums, the dictionary with ids in first-seen order
    (row-major, src before dst), the counters and the activity."""
    from repro.core.ops import mix32
    from repro.core.ref import ref_windowed_histogram
    from repro.stream import init_state, update_state

    rng = np.random.default_rng(11)
    n, batch, nw, bins = 1024, 256, 3, 32
    src = rng.integers(0, 90, n).astype(np.int32)
    dst = rng.integers(0, 90, n).astype(np.int32)
    win = rng.integers(0, nw, n).astype(np.int32)
    a = init_state(n, 2 * n, nw, bins)
    links, ids = {}, {}
    for s in range(0, n, batch):
        sl = slice(s, s + batch)
        a = update_state(a, jnp.asarray(src[sl]), jnp.asarray(dst[sl]),
                         jnp.asarray(win[sl]), batch, backend="xla")
        for w, u, v in zip(win[sl], src[sl], dst[sl]):
            links[(w, u, v)] = links.get((w, u, v), 0) + 1
            for ip in (u, v):
                ids.setdefault(ip, len(ids))
        keys = sorted(links)
        n_links, n_ips = len(keys), len(ids)
        assert (int(a.n_links), int(a.n_ips)) == (n_links, n_ips)
        assert (int(a.n_packets), int(a.n_batches)) == (s + batch,
                                                        s // batch + 1)
        assert int(a.overflow) == 0
        for f, col in (("win", 0), ("src", 1), ("dst", 2)):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f))[:n_links],
                [k[col] for k in keys], f)
        np.testing.assert_array_equal(np.asarray(a.packets)[:n_links],
                                      [links[k] for k in keys])
        values = sorted(ids)
        np.testing.assert_array_equal(np.asarray(a.ip_values)[:n_ips], values)
        np.testing.assert_array_equal(np.asarray(a.ip_ids)[:n_ips],
                                      [ids[v] for v in values])
        seen = slice(0, s + batch)
        act = np.asarray(mix32(jnp.asarray(src[seen]))).astype(np.int64) % bins
        np.testing.assert_array_equal(
            np.asarray(a.activity),
            ref_windowed_histogram(win[seen], act, nw, bins))
