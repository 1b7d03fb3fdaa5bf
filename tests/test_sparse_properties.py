"""Property-test tier for the GraphBLAS-lite CSR algebra.

Algebraic laws checked over randomized COO inputs (via the
``_hypothesis_compat`` shim — real ``hypothesis`` when installed, seeded
fixed examples otherwise):

  * ``ewise_union`` is associative, commutative, and has the empty matrix
    as identity — bit-identically, because all three reduce to the same
    sort-then-segment pipeline over the same coordinates;
  * ``from_coo`` is idempotent: rebuilding a CSR from its own entries is a
    bit-identical round-trip (the canonical-form fixed point);
  * ``mxv``/``vxm`` are dual through :func:`transpose` — exact for the
    min/max monoids, allclose for plus (summation order differs);
  * ``transpose``/``symmetrize``/min-monoid reductions agree with dense
    NumPy / scipy oracles;
  * ``entry_rows`` inverts the row pointers exactly as a binary search
    would, eager and under jit, and lowers to no loop.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.plan import sorted_edges
from repro.core.sparse import (
    CsrMatrix,
    csr_from_plan,
    ewise_union,
    from_coo,
    gather_rows,
    mxv,
    scatter_rows,
    symmetrize,
    transpose,
    vxm,
)

N_VERTS = 12  # compact key domain for all properties


def _coo(triples, cap=None):
    """CSR from [(row, col, val), ...] with deterministic capacity."""
    triples = list(triples)
    cap = cap if cap is not None else max(len(triples), 1)
    rows = np.full(cap, 0, np.int32)
    cols = np.full(cap, 0, np.int32)
    vals = np.zeros(cap, np.float32)
    for i, (r, c, v) in enumerate(triples):
        rows[i], cols[i], vals[i] = r, c, v
    csr, dropped = from_coo(
        [jnp.asarray(rows)], jnp.asarray(cols), jnp.asarray(vals),
        n_valid=jnp.asarray(len(triples), jnp.int32),
    )
    assert int(dropped) == 0
    return csr


def _dense(csr, n=N_VERTS):
    """float64 dense oracle view of the live entries."""
    out = np.zeros((n, n), np.float64)
    rk = np.asarray(csr.row_keys[0])
    rows = np.asarray(csr.entry_rows())
    mask = np.asarray(csr.entry_mask())
    cols = np.asarray(csr.col_keys)
    vals = np.asarray(csr.vals)
    for e in np.where(mask)[0]:
        out[rk[rows[e]], cols[e]] += vals[e]
    return out


def _entries(csr):
    """Canonical (rows, cols, vals, mask) tuple for bit-identity checks."""
    return (
        np.asarray(csr.entry_row_key(0)),
        np.asarray(csr.col_keys),
        np.asarray(csr.vals),
        np.asarray(csr.entry_mask()),
    )


def _assert_same_live(a: CsrMatrix, b: CsrMatrix):
    """Bit-identical live entries (capacities may differ)."""
    ra, ca, va, ma = _entries(a)
    rb, cb, vb, mb = _entries(b)
    assert ma.sum() == mb.sum()
    np.testing.assert_array_equal(ra[ma], rb[mb])
    np.testing.assert_array_equal(ca[ma], cb[mb])
    np.testing.assert_array_equal(va[ma], vb[mb])


triple_lists = st.lists(
    st.tuples(
        st.integers(0, N_VERTS - 1),
        st.integers(0, N_VERTS - 1),
        st.integers(1, 8),
    ),
    min_size=0,
    max_size=10,
)


# ------------------------------------------------------------ ewise_union

@given(triple_lists, triple_lists)
@settings(max_examples=12, deadline=None)
def test_ewise_union_commutative(ta, tb):
    a, b = _coo(ta), _coo(tb)
    ab, d1 = ewise_union(a, b, nnz_capacity=24, row_capacity=24)
    ba, d2 = ewise_union(b, a, nnz_capacity=24, row_capacity=24)
    assert int(d1) == int(d2) == 0
    _assert_same_live(ab, ba)


@given(triple_lists, triple_lists, triple_lists)
@settings(max_examples=12, deadline=None)
def test_ewise_union_associative(ta, tb, tc):
    a, b, c = _coo(ta), _coo(tb), _coo(tc)
    left, _ = ewise_union(
        ewise_union(a, b, nnz_capacity=24, row_capacity=24)[0], c,
        nnz_capacity=36, row_capacity=36)
    right, _ = ewise_union(
        a, ewise_union(b, c, nnz_capacity=24, row_capacity=24)[0],
        nnz_capacity=36, row_capacity=36)
    _assert_same_live(left, right)
    np.testing.assert_allclose(_dense(left), _dense(a) + _dense(b) + _dense(c))


@given(triple_lists)
@settings(max_examples=12, deadline=None)
def test_ewise_union_empty_identity(ts):
    a = _coo(ts)
    zero = _coo([], cap=4)
    out, dropped = ewise_union(a, zero, nnz_capacity=a.nnz_capacity)
    assert int(dropped) == 0
    _assert_same_live(out, a)


# ----------------------------------------------------- from_coo idempotence

@given(triple_lists, st.integers(0, 2))
@settings(max_examples=12, deadline=None)
def test_from_coo_idempotent(ts, op_ix):
    """Rebuilding a CSR from its own entries is a bit-identical no-op:
    from_coo output is already in canonical (sorted, dup-free) form, so a
    second pass has nothing to collapse under ANY dup op."""
    op = ("plus", "max", "min")[op_ix]
    first, _ = from_coo(
        [jnp.asarray(np.array([r for r, _, _ in ts] + [0], np.int32))],
        jnp.asarray(np.array([c for _, c, _ in ts] + [0], np.int32)),
        jnp.asarray(np.array([v for _, _, v in ts] + [0], np.float32)),
        n_valid=jnp.asarray(len(ts), jnp.int32),
        op=op,
    )
    again, dropped = from_coo(
        [first.entry_row_key(0)],
        first.col_keys,
        first.vals,
        valid_mask=first.entry_mask(),
        op=op,
        nnz_capacity=first.nnz_capacity,
        row_capacity=first.row_capacity,
    )
    assert int(dropped) == 0
    for fa, fb in zip(
        (np.asarray(first.indptr), *_entries(first)),
        (np.asarray(again.indptr), *_entries(again)),
    ):
        np.testing.assert_array_equal(fa, fb)


# ------------------------------------------------------- mxv / vxm duality

@given(triple_lists, st.integers(0, 2), st.integers(0, 1000))
@settings(max_examples=12, deadline=None)
def test_mxv_vxm_dual_via_transpose(ts, add_ix, xseed):
    """x ⊕.⊗ A == A^T ⊕.⊗ x (vertex domain): exact for min/max, allclose
    for plus (the two sides reduce in different entry orders)."""
    add = ("plus", "max", "min")[add_ix]
    ident = {"plus": 0.0, "max": -np.inf, "min": np.inf}[add]
    a = _coo(ts)
    at, dropped = transpose(a)
    assert int(dropped) == 0
    x = np.random.default_rng(xseed).uniform(0.5, 2.0, N_VERTS).astype(
        np.float32)

    via_vxm = np.asarray(vxm(
        gather_rows(a, jnp.asarray(x), fill=ident), a, N_VERTS, add=add,
        backend="xla",
    ))
    via_mxv = np.asarray(scatter_rows(
        at,
        mxv(at, jnp.asarray(x), add=add, backend="xla"),
        N_VERTS,
        fill=ident,
    ))
    # vertices with no incident entries: vxm reports the ⊕ identity,
    # scatter_rows reports fill=identity — comparable everywhere
    if add == "plus":
        np.testing.assert_allclose(via_vxm, via_mxv, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(via_vxm, via_mxv)


@given(triple_lists, st.integers(0, 1000))
@settings(max_examples=12, deadline=None)
def test_min_monoid_matches_dense_oracle(ts, xseed):
    """min-plus-style reduction (rides the max kernel by negation) against
    a dense float64 masked-min oracle."""
    a = _coo(ts)
    d = _dense(a)
    x = np.random.default_rng(xseed).uniform(0.5, 2.0, N_VERTS).astype(
        np.float32)
    got = np.asarray(mxv(a, jnp.asarray(x), add="min", mul="times",
                         backend="xla"))
    rk = np.asarray(a.row_keys[0])
    rmask = np.asarray(a.row_mask())
    for slot in range(a.row_capacity):
        if not rmask[slot]:
            assert got[slot] == np.inf
            continue
        nz = np.nonzero(d[rk[slot]])[0]
        want = np.inf if len(nz) == 0 else np.min(
            d[rk[slot], nz] * x[nz].astype(np.float64))
        np.testing.assert_allclose(got[slot], np.float32(want), rtol=1e-6)


# ----------------------------------------------- transpose / symmetrize

@given(triple_lists)
@settings(max_examples=12, deadline=None)
def test_transpose_matches_dense(ts):
    a = _coo(ts)
    at, dropped = transpose(a)
    assert int(dropped) == 0
    np.testing.assert_allclose(_dense(at), _dense(a).T)
    # involution on the live entries
    back, _ = transpose(at, nnz_capacity=a.nnz_capacity)
    np.testing.assert_allclose(_dense(back), _dense(a))


@given(triple_lists)
@settings(max_examples=12, deadline=None)
def test_symmetrize_matches_dense(ts):
    a = _coo(ts)
    sym, dropped = symmetrize(a)
    assert int(dropped) == 0
    d = _dense(a)
    np.testing.assert_allclose(_dense(sym), d + d.T)


def test_transpose_rejects_multi_key_rows():
    csr = _coo([(0, 1, 1.0)])
    multi = CsrMatrix(
        row_keys=(csr.row_keys[0], csr.row_keys[0]),
        indptr=csr.indptr, col_keys=csr.col_keys, vals=csr.vals,
        n_rows=csr.n_rows, nnz=csr.nnz,
    )
    with pytest.raises(ValueError, match="1-column row key"):
        transpose(multi)


# ------------------------------------------------------------- entry_rows

def _csr_from_degrees(degrees, row_capacity, nnz_capacity):
    """A CSR whose live rows hold ``degrees`` entries each (zeros allowed)."""
    n_rows, nnz = len(degrees), int(sum(degrees))
    indptr = np.full(row_capacity + 1, nnz, np.int32)
    indptr[: n_rows + 1] = np.concatenate([[0], np.cumsum(degrees)])
    keys = np.full(row_capacity, np.iinfo(np.int32).max, np.int32)
    keys[:n_rows] = np.arange(n_rows)
    cols = np.full(nnz_capacity, np.iinfo(np.int32).max, np.int32)
    cols[:nnz] = np.arange(nnz)
    return CsrMatrix(
        row_keys=(jnp.asarray(keys),), indptr=jnp.asarray(indptr),
        col_keys=jnp.asarray(cols), vals=jnp.zeros((nnz_capacity,), jnp.int32),
        n_rows=jnp.asarray(n_rows, jnp.int32), nnz=jnp.asarray(nnz, jnp.int32),
    )


def _random_edges(seed, n, cap, hi):
    rng = np.random.default_rng(seed)
    rows = np.full(cap, 0, np.int32)
    cols = np.full(cap, 0, np.int32)
    rows[:n] = rng.integers(0, hi, n)
    cols[:n] = rng.integers(0, hi, n)
    return jnp.asarray(rows), jnp.asarray(cols)


def _plan_case():
    rows, cols = _random_edges(3, 150, 200, 40)
    plan = sorted_edges(rows, cols, n_valid=150)
    return csr_from_plan(plan), np.asarray(plan.link_to_k0()[:plan.capacity])


def _coo_case():
    rows, cols = _random_edges(5, 90, 128, 25)
    csr, dropped = from_coo(
        [rows], cols, jnp.ones((128,), jnp.int32),
        n_valid=jnp.asarray(90, jnp.int32), nnz_capacity=96, row_capacity=48,
    )
    assert int(dropped) == 0
    return csr, None


ENTRY_ROWS_CASES = {
    "empty_rows_middle": lambda: (
        _csr_from_degrees([2, 0, 0, 3, 1, 0, 2], 9, 12), None),
    "empty_rows_first": lambda: (
        _csr_from_degrees([0, 0, 3, 0, 2], 6, 8), None),
    "nnz_zero": lambda: (_csr_from_degrees([], 5, 7), None),
    "nnz_zero_live_rows": lambda: (_csr_from_degrees([0, 0], 5, 7), None),
    "nnz_full": lambda: (_csr_from_degrees([3, 1, 0, 4], 6, 8), None),
    "rows_above_nnz": lambda: (_csr_from_degrees([1, 0, 2, 1], 16, 6), None),
    "rows_below_nnz": lambda: (_csr_from_degrees([7, 0, 9], 3, 20), None),
    "from_plan": _plan_case,
    "from_coo": _coo_case,
}


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", sorted(ENTRY_ROWS_CASES))
def test_entry_rows_inverts_row_pointers(case, jit):
    """Each entry's row is the binary search of its slot in the row
    pointers, ``row_capacity`` past ``nnz``; off a plan, it is also the
    plan's own link -> key0 map."""
    csr, link_to_k0 = ENTRY_ROWS_CASES[case]()
    f = jax.jit(lambda c: c.entry_rows()) if jit else (lambda c: c.entry_rows())
    got = np.asarray(f(csr))
    idx = np.arange(csr.nnz_capacity)
    want = np.searchsorted(np.asarray(csr.indptr), idx, side="right") - 1
    want = np.where(idx < int(csr.nnz), want, csr.row_capacity)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    if link_to_k0 is not None:
        np.testing.assert_array_equal(got, link_to_k0)


def test_entry_rows_lowers_to_no_loop():
    """The row map is a scatter and a prefix sum: no ``while`` (a binary
    search lowers to one of log2(rows) steps over every entry)."""
    csr = _csr_from_degrees(
        np.random.default_rng(0).integers(0, 4, 1 << 12), 1 << 12, 1 << 14)
    text = jax.jit(lambda c: c.entry_rows()).lower(csr).as_text()
    assert "while" not in text
