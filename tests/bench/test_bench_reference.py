"""The NumPy reference on cases worked out by hand."""
import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench import reference


def test_group_counts_rows_and_sums_weights():
    (s, d), count, total = reference.group(
        [np.array([2, 1, 2, 1, 2]), np.array([5, 3, 5, 4, 5])],
        np.array([1, 2, 3, 4, 5]))
    assert s.tolist() == [1, 1, 2] and d.tolist() == [3, 4, 5]
    assert count.tolist() == [1, 1, 3] and total.tolist() == [2, 4, 9]


def test_mix32_is_murmur3_finalizer():
    # murmur3 fmix32 with these constants maps 0 to 0 and is a bijection
    x = np.arange(1 << 16, dtype=np.uint32)
    h = reference.mix32(x)
    assert h[0] == 0 and len(np.unique(h)) == len(x)
    assert reference.mix32(np.array([-1], np.int32)).dtype == np.uint32


def test_window_ids_cover_the_span_in_equal_windows():
    w = reference.window_ids(np.array([10, 11, 14, 17, 18, 25]), 4)
    # span 16 -> windows of 4: [10,14) [14,18) [18,22) [22,26)
    assert w.tolist() == [0, 0, 1, 1, 2, 3]


def test_scalars_and_top_k_of_a_small_table():
    src = np.array([1, 1, 1, 2, 3, 3])
    dst = np.array([2, 2, 3, 3, 1, 2])
    a = reference.challenge_answers(src, dst, np.zeros(6), np.ones(6),
                                    n_windows=2, ip_bins=8, k=2)
    s = a["scalars"]
    assert (s["valid_packets"], s["unique_links"], s["max_link_packets"]) \
        == (6, 5, 2)
    assert (s["n_unique_sources"], s["max_source_packets"],
            s["max_source_fanout"]) == (3, 3, 2)
    assert (s["n_unique_destinations"], s["max_destination_fanin"]) == (3, 2)
    assert [x.tolist() for x in a["top"]] == [[1, 1], [2, 3], [2, 1]]
    assert a["windowed"]["valid_packets"].tolist() == [6, 0]
    assert a["overlap"].tolist() == [0, 0]
    assert a["activity"].sum() == 6


def test_anonymize_wrong_accepts_bijections_only():
    src, dst = np.array([10, 20, 30]), np.array([20, 30, 10])
    assert reference.anonymize_wrong(src, dst, np.array([2, 0, 1]),
                                     np.array([0, 1, 2])) == 0
    # identity is a function but not onto [0, n)
    assert reference.anonymize_wrong(src, dst, src, dst) > 0
    # not a function: 20 maps to two ids
    assert reference.anonymize_wrong(src, dst, np.array([2, 0, 1]),
                                     np.array([1, 1, 2])) > 0


def test_stable_ids_follow_first_sight_src_before_dst():
    vals, ids = reference.stable_ids(np.array([9, 4, 9]), np.array([4, 7, 1]))
    assert dict(zip(vals.tolist(), ids.tolist())) == {9: 0, 4: 1, 7: 2, 1: 3}


def test_collide_links_merges_only_equal_hashes():
    src = np.arange(1000)
    dst = np.arange(1000) * 7
    s2, d2 = reference.collide_links(src, dst)
    assert (s2 == src).all() and (d2 == dst).all()   # no collision here


def test_peaks_know_the_v5e_and_refuse_other_kinds():
    from bench import peaks

    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
