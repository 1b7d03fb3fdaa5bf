"""Windowed multi-temporal queries vs per-window oracle loop."""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import Table
from repro.core.ref import ref_run_all_queries
from repro.core.temporal import window_ids, windowed_queries

KEYMAP = {
    "valid_packets": "valid_packets",
    "unique_links": "unique_links",
    "max_link_packets": "max_link_packets",
    "n_unique_sources": "n_unique_sources",
    "n_unique_destinations": "n_unique_destinations",
    "max_source_packets": "max_source_packets",
    "max_source_fanout": "max_source_fanout",
    "max_destination_packets": "max_destination_packets",
    "max_destination_fanin": "max_destination_fanin",
}


def _check(src, dst, ts, window_len, n_windows, w=None):
    cols = {"src": src, "dst": dst, "ts": ts}
    if w is not None:
        cols["n_packets"] = w
    t = Table.from_dict({k: jnp.asarray(v) for k, v in cols.items()})
    res = jax.jit(
        lambda t: windowed_queries(t, window_len, n_windows)
    )(t)
    wid = (ts - ts.min()) // window_len
    for win in range(n_windows):
        sel = wid == win
        if not sel.any():
            for k in KEYMAP:
                assert int(res[k][win]) == 0, (k, win)
            continue
        ref = ref_run_all_queries(src[sel], dst[sel],
                                  None if w is None else w[sel])
        for ours, theirs in KEYMAP.items():
            assert int(res[ours][win]) == ref[theirs], (ours, win)


def test_windowed_matches_per_window_oracle():
    rng = np.random.default_rng(0)
    n = 4000
    src = rng.integers(0, 40, n).astype(np.int32)
    dst = rng.integers(0, 60, n).astype(np.int32)
    ts = np.sort(rng.integers(0, 1000, n)).astype(np.int32)
    _check(src, dst, ts, window_len=250, n_windows=4)


def test_windowed_weighted():
    rng = np.random.default_rng(1)
    n = 2000
    src = rng.integers(0, 30, n).astype(np.int32)
    dst = rng.integers(0, 30, n).astype(np.int32)
    ts = rng.integers(0, 600, n).astype(np.int32)
    w = rng.integers(1, 7, n).astype(np.int32)
    _check(src, dst, ts, window_len=200, n_windows=3, w=w)


@given(st.integers(1, 6), st.integers(50, 400))
@settings(max_examples=10, deadline=None)
def test_windowed_property(n_windows, window_len):
    rng = np.random.default_rng(n_windows * 1000 + window_len)
    n = 600
    src = rng.integers(0, 20, n).astype(np.int32)
    dst = rng.integers(0, 20, n).astype(np.int32)
    ts = rng.integers(0, window_len * n_windows, n).astype(np.int32)
    _check(src, dst, ts, window_len=window_len, n_windows=n_windows)


def test_window_ids_basics():
    ts = jnp.asarray(np.array([100, 149, 150, 299], np.int32))
    np.testing.assert_array_equal(np.asarray(window_ids(ts, 50)), [0, 0, 1, 3])


def test_window_ids_explicit_t0():
    """t0= pins the window origin instead of the column minimum — the
    streaming engine's contract (its link tables may not contain window 0
    mid-stream, and a min-derived origin would silently shift windows)."""
    ts = jnp.asarray(np.array([100, 149, 150, 299], np.int32))
    np.testing.assert_array_equal(np.asarray(window_ids(ts, 50, t0=0)),
                                  [2, 2, 3, 5])
    # ts already holding window ids: t0=0, window_len=1 is the identity
    wid = jnp.asarray(np.array([3, 0, 2, 2], np.int32))
    np.testing.assert_array_equal(np.asarray(window_ids(wid, 1, t0=0)),
                                  [3, 0, 2, 2])
    # negative origin offsets work (timestamps before t0 -> negative ids,
    # callers clip); windowed_queries clips them into window 0
    np.testing.assert_array_equal(np.asarray(window_ids(ts, 50, t0=200)),
                                  [-2, -2, -1, 1])


def test_windowed_queries_empty_table():
    """n_valid == 0: every statistic is 0 in every window."""
    t = Table.from_dict(
        {"src": np.zeros(16, np.int32), "dst": np.zeros(16, np.int32),
         "ts": np.zeros(16, np.int32)}, n_valid=0)
    res = jax.jit(lambda t: windowed_queries(t, 10, 4))(t)
    for k, v in res.items():
        assert v.shape == (4,)
        np.testing.assert_array_equal(np.asarray(v), 0, err_msg=k)


def test_windowed_queries_t0_pins_origin():
    """Same rows shifted in time: with t0= the suite is invariant, without
    it the min-derived origin would re-bucket rows identically anyway —
    but a *missing* early window must not shift later ones."""
    rng = np.random.default_rng(9)
    n = 400
    src = rng.integers(0, 20, n).astype(np.int32)
    dst = rng.integers(0, 20, n).astype(np.int32)
    win = rng.integers(1, 3, n).astype(np.int32)   # window 0 never occurs
    t = Table.from_dict({"src": src, "dst": dst, "ts": win})
    res = windowed_queries(t, 1, 4, t0=0)
    assert int(res["valid_packets"][0]) == 0       # window 0 stays empty
    assert int(res["valid_packets"].sum()) == n
    # without t0 the min (=1) becomes the origin and everything shifts
    shifted = windowed_queries(t, 1, 4)
    np.testing.assert_array_equal(np.asarray(shifted["valid_packets"])[:2],
                                  np.asarray(res["valid_packets"])[1:3])


def test_windows_concatenate_to_global():
    """Σ_w valid_packets[w] == global count (conservation property)."""
    rng = np.random.default_rng(2)
    n = 3000
    src = rng.integers(0, 50, n).astype(np.int32)
    dst = rng.integers(0, 50, n).astype(np.int32)
    ts = rng.integers(0, 900, n).astype(np.int32)
    t = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst),
                         "ts": jnp.asarray(ts)})
    res = windowed_queries(t, 100, 9)
    assert int(res["valid_packets"].sum()) == n
