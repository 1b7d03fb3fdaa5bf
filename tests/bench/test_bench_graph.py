"""The Graphalytics cell at a tiny size on the CPU: a sound run comes out
correct and reports its metrics, a planted wrong BFS level, WCC label,
scaled rank, unconverged kernel or PageRank stopped early makes it not
correct, each control reads over its limit, and the reference agrees with
the program's own oracles."""
import dataclasses

import numpy as np
import pytest

from bench_tiny import benchmark, run_driver, run_tiny, tiny_root

CELL = "graphalytics-g500s18"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


def test_window_loop_rate_and_spans(root):
    ctx, res = run_driver(root, CELL, seconds=0.5)
    w = res.obs["window"]
    assert w["wall_s"] >= 0.5 and w["passes"] >= 1
    assert w["packets"] == w["passes"] * ctx.traffic["n_packets"]
    assert res.e2e["challenge_packets_per_s"] == w["packets"] / w["wall_s"]
    spans = res.obs["spans"]
    for name in ("read", "build_host", "build_device", "anonymize", "graph",
                 "graph/plan", "graph/bfs", "graph/wcc", "graph/pagerank"):
        assert len(spans[name]) == w["passes"], name
    assert "analyze" not in spans
    assert all(len(res.obs["iterations"][k]) == w["passes"]
               for k in ("bfs", "wcc", "pagerank"))
    assert all(v <= lim for v, lim in res.checks.values())


def test_sync_walls_name_each_phase_wait():
    from bench.drivers.graph import _sync_walls

    def span(seq, parent, name, d):
        return {"kind": "span", "seq": seq, "parent": parent, "name": name,
                "duration_s": d}

    records = [span(0, "challenge/anonymize", "sync", 9.0),
               span(1, "challenge/anonymize", "sync", 1.5),
               span(2, "challenge/anonymize", "dispatch", 0.1),
               span(3, "challenge/graph/plan", "sync", 1.25),
               span(4, "challenge/anonymize", "sync", 1.75),
               span(5, "stream.pass", "sync", 7.0),
               {"kind": "counter", "seq": 6, "parent": "challenge/graph/bfs",
                "name": "sync", "value": 1}]
    assert _sync_walls(records, after=0) == {"anonymize/sync": [1.5, 1.75],
                                             "graph/plan/sync": [1.25]}


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct_and_reports_its_metrics(root, trace):
    from bench import harness

    line = run_tiny(root, CELL, trace=trace)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == {"anonymize_wrong", "build_wrong",
                                   "bfs_wrong", "wcc_wrong", "pagerank_l1",
                                   "pagerank_steps_off", "unconverged"}
    e2e, layer = harness.cell_metrics(benchmark(), CELL)
    if trace:
        # the CPU has no device plane, so only the idle share stays silent
        want = {m["name"] for m in layer
                if not m["name"].startswith("device_idle_share")}
        assert set(line["metrics"]) == want
        assert want == {"graph_s", "bfs_step_s", "wcc_step_s",
                        "pagerank_step_s"}
    else:
        assert set(line["metrics"]) == {"setup_s", "challenge_packets_per_s"}


def _altered(kernel, change):
    def plant(mp):
        import repro.challenge.pipeline as pipeline

        real = pipeline.graph_kernel

        def altered(g, **kw):
            out = real(g, **kw)
            return change(out) if kw["kernel"] == kernel else out

        mp.setattr(pipeline, "graph_kernel", altered)
    return plant


def _stopped_early(mp):
    """PageRank that stops after two steps and reports itself converged."""
    import repro.challenge.pipeline as pipeline

    real = pipeline.graph_kernel

    def altered(g, **kw):
        if kw["kernel"] != "pagerank":
            return real(g, **kw)
        out = real(g, max_iters=2, **kw)
        return dataclasses.replace(out, converged=out.converged | True)

    mp.setattr(pipeline, "graph_kernel", altered)


FAULTS = {
    "bfs_level": (_altered("bfs", lambda r: dataclasses.replace(
        r, levels=r.levels.at[1].add(1))), "bfs_wrong"),
    "wcc_label": (_altered("wcc", lambda r: dataclasses.replace(
        r, labels=r.labels.at[2].add(1))), "wcc_wrong"),
    "scaled_rank": (_altered("pagerank", lambda r: dataclasses.replace(
        r, ranks=r.ranks * 1.001)), "pagerank_l1"),
    "unconverged": (_altered("wcc", lambda r: dataclasses.replace(
        r, converged=r.converged & False)), "unconverged"),
    "pagerank_early_stop": (_stopped_early, "pagerank_steps_off"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_planted_fault_is_not_correct(root, monkeypatch, fault):
    plant, check = FAULTS[fault]
    plant(monkeypatch)
    line = run_tiny(root, CELL)
    assert line["correct"] is False
    c = line["checks"][check]
    assert c["value"] > c["limit"], line["checks"]


@pytest.fixture(scope="module")
def ctl_root(tmp_path_factory):
    # past 2048 ids, so that float16 labels lose some
    return tiny_root(tmp_path_factory.mktemp("ctl"), n_packets=1 << 16,
                     scale=14)


@pytest.mark.parametrize("control,check", [
    ("labels_f16", "wcc_wrong"), ("ranks_bf16", "pagerank_l1"),
    ("bfs_undirected", "bfs_wrong")])
@pytest.mark.parametrize("seed", [7, 2**31 + 7])
def test_control_is_not_correct(ctl_root, control, check, seed):
    from bench.graph_control import control_checks

    checks = control_checks(CELL, seed, control, ctl_root)
    value, limit = checks[check]
    assert value > limit, checks
    assert all(v <= lim for k, (v, lim) in checks.items() if k != check)


def test_reference_matches_the_program_oracles():
    """On a small graph the benchmark's reference and the program's NumPy
    oracles (``kernels/ref.py``, written apart) give the same levels,
    labels and, for unweighted links, ranks."""
    from bench import graph_reference as gr
    from repro.kernels.ref import ref_bfs, ref_cc, ref_pagerank

    rng = np.random.default_rng(3)
    n = 300
    src = rng.integers(0, n, 900)
    dst = (src + rng.integers(-5, 6, 900)) % n  # many components, long paths
    u, v = gr.links(src, dst)
    for s in (0, 17, 211):
        np.testing.assert_array_equal(gr.bfs_levels(u, v, n, s),
                                      ref_bfs(u, v, n, s))
    np.testing.assert_array_equal(gr.wcc_labels(u, v, n), ref_cc(u, v, n))
    want, steps, _ = ref_pagerank(u, v, np.ones(len(u)), n)
    got, got_steps = gr.pagerank(u, v, n, 100, damping=0.85, tol=1e-6)
    assert got_steps == steps
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)
    assert gr.checks({"bfs_levels": gr.bfs_levels(u, v, n, 17),
                      "wcc_labels": ref_cc(u, v, n),
                      "pagerank_ranks": want, "pagerank_steps": steps},
                     u, v, n, 17, damping=0.85, tol=1e-6, max_steps=100) == {
        "bfs_wrong": 0, "wcc_wrong": 0, "pagerank_l1": 0.0,
        "pagerank_steps_off": 0, "unconverged": 0}


def test_idle_share_reader_reads_as_the_batch_cell():
    from bench import harness

    graph = harness.load_module("metrics", "device_idle_share.graph")
    batch = harness.load_module("metrics", "device_idle_share.batch")
    for obs in ({"trace": {"idle_share": 0.0623, "busy_s": 5.5}},
                {"trace": None}, {}):
        assert graph.read(obs) == batch.read(obs)
    assert graph.read({"trace": {"idle_share": 0.05, "busy_s": 1.0}}) == 5.0
