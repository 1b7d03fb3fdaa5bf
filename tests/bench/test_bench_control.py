"""The controls of ``correct`` come out not correct; the reference itself does.

The exact cells' control packs link keys into 32 bits, so it fails only
where two links collide: at 2^18 packets several pairs do.  The sketch
cell's controls hold counters in int16, which fails once a count passes
2^15, or estimate the maxima with a Count-Min of one row in place of four.
"""
import numpy as np
import pytest

from bench_tiny import tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("ctl"), n_packets=1 << 18,
                     scale=18)


@pytest.mark.parametrize("cell,passes,control", [
    ("batch-rmat22", 1, "hashed_links"),
    ("service-exact-rmat20", 4, "hashed_links"),
    ("service-sketch-rmat20", 3, "int16"),
    ("service-sketch-rmat20", 3, "cms_depth1")])
@pytest.mark.parametrize("seed", [7, 2**31 + 7])
def test_control_is_not_correct(root, cell, passes, control, seed):
    from bench.control import control_checks

    checks = control_checks(cell, seed, passes, root, control=control)
    assert any(v > lim for v, lim in checks.values()), checks
    if control == "cms_depth1":
        # the one-row sketch fails through the maxima alone
        assert checks["max_gap_share"][0] > checks["max_gap_share"][1]
        assert checks["hll_err_share"][0] == checks["packets_gap"][0] == 0


def test_reference_against_itself_is_correct(root):
    from bench import harness, reference
    from bench.traffic import generate

    t = harness.load_json("traffic", "rmat20-backlog", root)
    cols = generate(t, 11)
    src, dst = cols["src"].astype(np.int64), cols["dst"].astype(np.int64)
    win = reference.window_ids(cols["ts"], 8)
    kw = dict(n_windows=8, ip_bins=1024)
    a = reference.challenge_answers(src, dst, win, np.ones(len(src)), k=10,
                                    **kw)
    assert not any(reference.compare_challenge(a, a).values())
    one = reference.stream_state(src, dst, win, batches_per_pass=4, **kw)
    st = reference.scale_state(one, 3)
    assert not any(reference.compare_stream_state(st, st).values())
    exact = reference.exact_counts(src, dst)
    truth = reference.sketch_truth(exact, 3, 10)
    cfg = harness.load_json("configs", "sensor-service-sketch", root)
    worst = reference.sketch_checks([truth], exact, passes=[3], cfg=cfg)
    assert all(v == 0 for v in worst.values())
    # a Count-Min of the configured four rows over one pass stays in bounds
    deep = dict(truth, **{
        "max_link_packets": reference.count_min_max(
            exact["link_keys"], exact["link_packets"] * 3, heavy=64,
            depth=4, width=cfg["cms_width"]),
        "max_source_packets": reference.count_min_max(
            exact["src_keys"], exact["src_packets"] * 3, heavy=64,
            depth=4, width=cfg["cms_width"])})
    worst = reference.sketch_checks([deep], exact, passes=[3], cfg=cfg)
    assert worst["max_gap_share"] <= 1.0
