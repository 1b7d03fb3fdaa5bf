"""End-to-end pipeline tests: repro.challenge phases vs the NumPy oracle,
plus the new semi-join / isin / top-k relational ops."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.challenge import (
    ChallengeConfig,
    analyze,
    cross_window_ip_overlap,
    run_challenge,
)
from repro.challenge.pipeline import build_columns, build_table, window_column
from repro.core import Table, isin, semi_join, top_k, top_links, unique
from repro.core.ref import (
    ref_anonymize_check,
    ref_isin,
    ref_run_all_queries,
    ref_semi_join,
    ref_top_links,
    ref_window_ip_overlap,
    ref_windowed_histogram,
)
from repro.kernels.ops import windowed_histogram

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------- new core ops

def test_isin_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, 200).astype(np.int32)
    vals = rng.integers(0, 64, 50).astype(np.int32)
    u = unique(jnp.asarray(np.concatenate([vals, np.zeros(14, np.int32)])),
               n_valid=50)
    got = np.asarray(isin(jnp.asarray(x), u.values, u.n_unique, n_valid=180))
    ref = ref_isin(x[:180], vals)
    np.testing.assert_array_equal(got[:180], ref)
    assert not got[180:].any()


@pytest.mark.parametrize("ln,rn", [(0, 0), (1, 0), (0, 1), (120, 60), (64, 64)])
def test_semi_join_matches_numpy(ln, rn):
    rng = np.random.default_rng(ln * 100 + rn)
    lcap, rcap = ln + 9, rn + 5
    ls = rng.integers(0, 9, lcap).astype(np.int32)
    ld = rng.integers(0, 9, lcap).astype(np.int32)
    rs = rng.integers(0, 9, rcap).astype(np.int32)
    rd = rng.integers(0, 9, rcap).astype(np.int32)
    got = np.asarray(jax.jit(
        lambda a, b, c, d: semi_join([a, b], [c, d],
                                     left_n_valid=ln, right_n_valid=rn)
    )(*map(jnp.asarray, (ls, ld, rs, rd))))
    ref = ref_semi_join([ls[:ln], ld[:ln]], [rs[:rn], rd[:rn]])
    np.testing.assert_array_equal(got[:ln], ref)
    assert not got[ln:].any()


def test_top_k_ties_prefer_lowest_index():
    vals, idx, n = top_k(jnp.asarray(np.array([3, 9, 9, 1, 9], np.int32)), 4)
    assert int(n) == 4
    np.testing.assert_array_equal(np.asarray(idx)[:3], [1, 2, 4])
    np.testing.assert_array_equal(np.asarray(vals), [9, 9, 9, 3])


def test_top_k_fewer_live_than_k():
    mask = jnp.asarray(np.array([True, True, False, False]))
    vals, idx, n = top_k(jnp.asarray(np.array([5, 7, 100, 100], np.int32)), 3,
                         valid_mask=mask)
    assert int(n) == 2
    np.testing.assert_array_equal(np.asarray(vals)[:2], [7, 5])
    np.testing.assert_array_equal(np.asarray(idx)[:2], [1, 0])


def test_top_links_matches_numpy():
    rng = np.random.default_rng(3)
    n, cap = 400, 421
    src = rng.integers(0, 10, n).astype(np.int32)
    dst = rng.integers(0, 10, n).astype(np.int32)
    pad = lambda a: np.concatenate([a, np.full(cap - n, 7, np.int32)])
    t = Table.from_dict({"src": pad(src), "dst": pad(dst)}, n_valid=n)
    tl = jax.jit(lambda t: top_links(t, 8))(t)
    k = int(tl.n_valid)
    es, ed, ep = ref_top_links(src, dst, 8)
    assert k == len(es)
    np.testing.assert_array_equal(np.asarray(tl.src)[:k], es)
    np.testing.assert_array_equal(np.asarray(tl.dst)[:k], ed)
    np.testing.assert_array_equal(np.asarray(tl.packets)[:k], ep)


# ------------------------------------------------------ windowed histogram

def test_windowed_histogram_one_dispatch_matches_numpy():
    rng = np.random.default_rng(4)
    n, nw, nb = 3000, 5, 64
    win = rng.integers(0, nw, n).astype(np.int32)
    ids = rng.integers(-1, nb, n).astype(np.int32)  # includes dropped rows
    w = rng.integers(1, 4, n).astype(np.float32)
    got = np.asarray(jax.jit(
        lambda a, b, c: windowed_histogram(a, b, nw, nb, weights=c,
                                           backend="xla")
    )(*map(jnp.asarray, (win, ids, w))))
    np.testing.assert_allclose(got, ref_windowed_histogram(win, ids, nw, nb, w))


def test_windowed_histogram_interpret_backend_agrees():
    rng = np.random.default_rng(5)
    n, nw, nb = 512, 3, 32
    win = rng.integers(0, nw, n).astype(np.int32)
    ids = rng.integers(0, nb, n).astype(np.int32)
    a = windowed_histogram(jnp.asarray(win), jnp.asarray(ids), nw, nb,
                           backend="xla")
    b = windowed_histogram(jnp.asarray(win), jnp.asarray(ids), nw, nb,
                           backend="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- pipeline phases

def _small_cfg(tmp_path, **kw) -> ChallengeConfig:
    base = dict(scale=10, n_windows=3, ip_bins=64, top_k=5,
                workdir=str(tmp_path))
    base.update(kw)
    return ChallengeConfig(**base)


def test_challenge_scalars_match_oracle(tmp_path):
    run = run_challenge(_small_cfg(tmp_path))
    ref = ref_run_all_queries(run.capture["src"].astype(np.int64),
                              run.capture["dst"].astype(np.int64))
    for k, v in ref.items():
        assert int(getattr(run.results.scalars, k)) == v, k
    # timings populated and positive
    for p in ("read", "build", "anonymize", "analyze"):
        assert getattr(run.timings, f"{p}_s") > 0, p
    assert run.timings.n_packets == 1 << 10
    assert run.timings.packets_per_s() > 0


def test_challenge_anonymization_is_isomorphism(tmp_path):
    run = run_challenge(_small_cfg(tmp_path, method="hash", rounds=2))
    n = run.timings.n_packets
    # reconstruct the anonymized row ids from the heaviest-link check:
    # anonymize invariance of the link-multiset is covered by the scalar
    # check; here verify the windowed suite agrees per window too.
    win = window_column(run.capture["ts"], run.config.n_windows)
    for w in range(run.config.n_windows):
        sel = win == w
        ref = ref_run_all_queries(run.capture["src"][sel].astype(np.int64),
                                  run.capture["dst"][sel].astype(np.int64))
        for k in ("valid_packets", "unique_links", "n_unique_sources",
                  "max_source_fanout", "max_destination_fanin"):
            assert int(run.results.windowed[k][w]) == ref[k], (k, w)


def test_challenge_vector_queries_match_oracle(tmp_path):
    """Vector phase outputs vs the oracle (anonymization-invariant parts)."""
    run = run_challenge(_small_cfg(tmp_path))
    src = run.capture["src"].astype(np.int64)
    dst = run.capture["dst"].astype(np.int64)
    r = run.results
    # multisets of per-group aggregates are isomorphism-invariant
    k = int(r.links.n_groups)
    _, _, ref_pk = __import__("repro.core.ref", fromlist=["ref_traffic_matrix"]
                              ).ref_traffic_matrix(src, dst)
    assert sorted(np.asarray(r.links.aggs["packets"])[:k].tolist()) == \
        sorted(ref_pk.tolist())
    es, ed, ep = ref_top_links(src, dst, run.config.top_k)
    kk = int(r.top.n_valid)
    np.testing.assert_array_equal(np.asarray(r.top.packets)[:kk], ep)


def test_challenge_window_overlap_and_activity(tmp_path):
    run = run_challenge(_small_cfg(tmp_path))
    win = window_column(run.capture["ts"], run.config.n_windows)
    ref_ov = ref_window_ip_overlap(run.capture["src"].astype(np.int64),
                                   run.capture["dst"].astype(np.int64),
                                   win, run.config.n_windows)
    np.testing.assert_array_equal(np.asarray(run.results.window_ip_overlap),
                                  ref_ov)
    # activity histogram conserves packets per window
    act = np.asarray(run.results.window_activity)
    np.testing.assert_array_equal(
        act.sum(axis=1).astype(np.int64),
        np.asarray(run.results.windowed["valid_packets"]).astype(np.int64),
    )


def test_cross_window_overlap_direct():
    rng = np.random.default_rng(9)
    n, cap, nw = 600, 640, 4
    src = rng.integers(0, 30, n).astype(np.int32)
    dst = rng.integers(10, 40, n).astype(np.int32)
    win = rng.integers(0, nw, n).astype(np.int32)
    pad = lambda a: np.concatenate([a, np.zeros(cap - n, np.int32)])
    t = Table.from_dict({"src": pad(src), "dst": pad(dst), "win": pad(win)},
                        n_valid=n)
    got = np.asarray(jax.jit(
        lambda t: cross_window_ip_overlap(t, nw, backend="xla"))(t))
    np.testing.assert_array_equal(got, ref_window_ip_overlap(src, dst, win, nw))


def test_challenge_capacity_padding(tmp_path):
    """Static capacity above n_packets must not change any result."""
    cfg = _small_cfg(tmp_path, capacity=(1 << 10) + 137)
    run = run_challenge(cfg)
    ref = ref_run_all_queries(run.capture["src"].astype(np.int64),
                              run.capture["dst"].astype(np.int64))
    for k, v in ref.items():
        assert int(getattr(run.results.scalars, k)) == v, k


def test_challenge_pcaplite_format(tmp_path):
    run = run_challenge(_small_cfg(tmp_path, fmt="pcaplite"))
    assert int(run.results.scalars.valid_packets) == 1 << 10


def test_challenge_fused_program(tmp_path):
    run = run_challenge(_small_cfg(tmp_path, fused=True))
    assert run.timings.fused_s is not None and run.timings.fused_s > 0
    assert "fused" in run.timings.format_table()


def test_challenge_read_cache_reuses_capture(tmp_path):
    cfg = _small_cfg(tmp_path)
    run1 = run_challenge(cfg)
    run2 = run_challenge(cfg)  # second run hits the cached capture file
    np.testing.assert_array_equal(run1.capture["src"], run2.capture["src"])
    for k in ref_run_all_queries(run1.capture["src"], run1.capture["dst"]):
        assert int(getattr(run1.results.scalars, k)) == \
            int(getattr(run2.results.scalars, k)), k


# one case per static of the phase programs' cache key: (base config, the
# static changed, the phase whose program that static shapes)
_STATIC_CASES = {
    "method": ({}, {"method": "hash"}, "anonymize"),
    "rounds": ({}, {"rounds": 2}, "anonymize"),
    "n_windows": ({}, {"n_windows": 2}, "analyze"),
    "ip_bins": ({}, {"ip_bins": 32}, "analyze"),
    "top_k": ({}, {"top_k": 3}, "analyze"),
    "backend": ({}, {"backend": "xla"}, "analyze"),
    "fused_epilogue": ({}, {"fused_epilogue": True}, "analyze"),
    "algorithms": ({}, {"algorithms": True}, "analyze"),
    "bfs_source": ({"algorithms": True}, {"bfs_source": 5}, "analyze"),
}


@pytest.mark.parametrize("static", list(_STATIC_CASES))
def test_phase_program_cache_keys_every_static(tmp_path, static):
    """A run with one static changed after a run with it at its default
    builds the changed phase's program anew and answers as ``anonymize``
    and ``analyze`` jitted afresh with that static."""
    from repro.core.anonymize import anonymize
    from repro.obs import get_tracer, reset_tracer

    base, change, phase = _STATIC_CASES[static]
    # a capacity no other test compiles, so the changed program is new here
    common = dict(scale=8, n_packets=256, capacity=256 + 19, n_windows=3,
                  ip_bins=64, top_k=5, warm=False, workdir=str(tmp_path))
    run_challenge(ChallengeConfig(**{**common, **base}))
    cfg = ChallengeConfig(**{**common, **base, **change})
    reset_tracer()
    run = run_challenge(cfg)
    traced = {r["parent"] for r in get_tracer().records()
              if r["kind"] == "counter" and r["name"] == "jit.trace_s"}
    reset_tracer()
    assert f"challenge/{phase}/dispatch" in traced
    assert not {f"challenge/{p}/dispatch"
                for p in ("build_device", "anonymize", "analyze")
                if p != phase} & traced

    src, dst, win, n = build_columns(run.capture, cfg)
    anon = jax.jit(lambda t, k: anonymize(
        t, k, method=cfg.method, rounds=cfg.rounds))(
        build_table(src, dst, win, n), jax.random.key(cfg.seed))
    want = jax.jit(lambda t: analyze(
        t, n_windows=cfg.n_windows, ip_bins=cfg.ip_bins, k=cfg.top_k,
        backend=cfg.backend, fused_epilogue=cfg.fused_epilogue,
        algorithms=cfg.algorithms, bfs_source=cfg.bfs_source))(anon.table)
    for got, ref in ((run.anon_table, anon.table), (run.results, want)):
        assert jax.tree.structure(got) == jax.tree.structure(ref)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_analyze_is_one_jittable_call():
    rng = np.random.default_rng(11)
    n, cap = 500, 512
    cols = {k: np.concatenate([rng.integers(0, 40, n).astype(np.int32),
                               np.zeros(cap - n, np.int32)])
            for k in ("src", "dst")}
    cols["win"] = np.concatenate([rng.integers(0, 3, n).astype(np.int32),
                                  np.zeros(cap - n, np.int32)])
    t = Table.from_dict(cols, n_valid=n)
    res = jax.jit(
        lambda t: analyze(t, n_windows=3, ip_bins=32, k=4, backend="xla")
    )(t)
    ref = ref_run_all_queries(cols["src"][:n], cols["dst"][:n])
    for k, v in ref.items():
        assert int(getattr(res.scalars, k)) == v, k


def test_cli_main_smoke(tmp_path, capsys):
    from repro.challenge.run import main

    rc = main(["--scale", "9", "--windows", "2", "--ip-bins", "32",
               "--top-k", "3", "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "14 max destination fan-in" in out
    assert "all scalar queries match the NumPy oracle" in out


@pytest.mark.parametrize("entry", ["function", "challenge_cli", "stream_cli"])
def test_distributed_refuses_one_device(entry, tmp_path, monkeypatch):
    """--distributed on one device raises instead of quietly running the
    single-device path."""
    from repro.challenge.pipeline import distributed_scalar_queries
    from repro.challenge.run import main as challenge_main
    from repro.stream.run import main as stream_main

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    common = ["--scale", "9", "--windows", "2", "--workdir", str(tmp_path),
              "--distributed"]
    call = {
        "function": lambda: distributed_scalar_queries(Table.from_dict(
            {"src": np.arange(8, dtype=np.int32),
             "dst": np.arange(8, dtype=np.int32)}, n_valid=8)),
        "challenge_cli": lambda: challenge_main(common),
        "stream_cli": lambda: stream_main(common + ["--batches", "2"]),
    }[entry]
    with pytest.raises(RuntimeError, match=r">= 2 devices, found 1"):
        call()
