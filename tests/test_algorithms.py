"""Iterative graph algorithms (core/algorithms.py): fixed-point harness
semantics, NumPy-oracle parity at scales 10 and 14, edge-case behaviour
(sentinels, dangling mass, cap-outs), streaming-vs-batch equivalence, and
the challenge's graph phase: its sort budget, spans and counters, operand
BFS source, and agreement with both the program's oracles and the
benchmark's reference."""
import dataclasses
import functools
import importlib.util
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.challenge.pipeline import (
    GRAPH_KERNELS,
    Analytics,
    ChallengeConfig,
    analyze,
    graph_kernel,
    graph_plan,
    graph_vertices,
    run_challenge,
)
from repro.core import (
    Table,
    UNREACHABLE,
    bfs_levels,
    connected_components,
    count_hlo_sorts,
    fixed_point,
    graph_algorithms,
    pagerank,
    table_csrs,
    table_plans,
    triangle_counts,
)
from repro.kernels.ref import ref_bfs, ref_cc, ref_pagerank, ref_triangles

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------- fixtures

def _graph_table(src, dst, nv=None):
    """Compact-id edge table + its CSR pair (the anonymized-graph regime)."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    t = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst)})
    csr_src, csr_dst = table_csrs(t)
    if nv is None:
        nv = int(max(src.max(), dst.max())) + 1 if len(src) else 1
    return src, dst, csr_src, csr_dst, nv


@functools.lru_cache(maxsize=None)
def _rmat_results(scale):
    """(src, dst, nv, AlgorithmResults) for a compacted RMAT capture."""
    from repro.data.rmat import synthetic_packets

    cols = synthetic_packets(1 << scale, scale=scale, seed=0)
    uniq = np.unique(np.concatenate([cols["src"], cols["dst"]]))
    src = np.searchsorted(uniq, cols["src"]).astype(np.int32)
    dst = np.searchsorted(uniq, cols["dst"]).astype(np.int32)
    _, _, csr_src, csr_dst, nv = _graph_table(src, dst)
    res = jax.jit(
        lambda a, b: graph_algorithms(a, b, len(uniq), source=0, backend="xla")
    )(csr_src, csr_dst)
    jax.block_until_ready(res)
    return src, dst, len(uniq), res


# ------------------------------------------------------ fixed-point harness

def test_fixed_point_scalar_contraction_known_count():
    # x_{k+1} = x_k / 2 from 1024 crosses 1.0 after exactly 10 halvings
    fp = fixed_point(
        lambda x: x / 2.0, jnp.float32(1024.0), 100,
        lambda old, new: new <= 1.0,
    )
    assert int(fp.iterations) == 10
    assert bool(fp.converged)
    assert float(fp.state) == 1.0


def test_fixed_point_non_convergent_stops_exactly_at_cap():
    fp = fixed_point(
        lambda x: x + 1.0, jnp.float32(3.0), 7,
        lambda old, new: jnp.zeros((), bool),
    )
    assert int(fp.iterations) == 7
    assert not bool(fp.converged)
    assert float(fp.state) == 10.0  # partial state is well-formed

    zero = fixed_point(
        lambda x: x + 1.0, jnp.float32(3.0), 0,
        lambda old, new: jnp.ones((), bool),
    )
    assert int(zero.iterations) == 0
    assert not bool(zero.converged)
    assert float(zero.state) == 3.0


def test_fixed_point_negative_cap_rejected():
    with pytest.raises(ValueError):
        fixed_point(lambda x: x, jnp.float32(0.0), -1, lambda o, n: True)


def test_fixed_point_state_survives_jit_retracing():
    """Pytree state threads through jit, including across a re-trace."""

    def solve(v, bias):
        return fixed_point(
            lambda s: {"x": s["x"] / 2.0 + bias, "steps": s["steps"] + 1},
            {"x": v, "steps": jnp.zeros((), jnp.int32)},
            50,
            lambda old, new: jnp.max(jnp.abs(new["x"] - old["x"])) < 1e-4,
        )

    f = jax.jit(solve)
    a = f(jnp.full((4,), 16.0, jnp.float32), 1.0)  # fixed point x = 2*bias
    assert bool(a.converged)
    np.testing.assert_allclose(np.asarray(a.state["x"]), 2.0, atol=1e-3)
    assert int(a.state["steps"]) == int(a.iterations)

    # different shape forces a re-trace; the carried pytree must survive
    b = f(jnp.full((7,), -8.0, jnp.float32), 3.0)
    assert bool(b.converged)
    np.testing.assert_allclose(np.asarray(b.state["x"]), 6.0, atol=1e-3)
    assert int(b.state["steps"]) == int(b.iterations)


# --------------------------------------------- oracle parity, scales 10/14

@pytest.mark.parametrize("scale", [10, 14])
def test_bfs_matches_oracle(scale):
    src, dst, nv, res = _rmat_results(scale)
    np.testing.assert_array_equal(
        np.asarray(res.bfs.levels), ref_bfs(src, dst, nv, 0)
    )
    assert bool(res.bfs.converged)
    lv = np.asarray(res.bfs.levels)
    assert int(res.bfs.n_reached) == int((lv >= 0).sum())
    # iterations = eccentricity + empty-frontier confirmation pass
    assert int(res.bfs.iterations) == int(lv.max()) + 1


@pytest.mark.parametrize("scale", [10, 14])
def test_connected_components_match_oracle(scale):
    src, dst, nv, res = _rmat_results(scale)
    want = ref_cc(src, dst, nv)
    np.testing.assert_array_equal(np.asarray(res.components.labels), want)
    assert int(res.components.n_components) == len(np.unique(want))
    assert bool(res.components.converged)


@pytest.mark.parametrize("scale", [10, 14])
def test_pagerank_matches_oracle_within_1e6(scale):
    src, dst, nv, res = _rmat_results(scale)
    want, ref_iters, ref_conv = ref_pagerank(src, dst, np.ones(len(src)), nv)
    ranks = np.asarray(res.pagerank.ranks)
    assert np.abs(ranks - want).sum() < 1e-6
    assert bool(res.pagerank.converged) and ref_conv
    assert int(res.pagerank.iterations) == ref_iters
    assert abs(ranks.sum() - 1.0) < 1e-5  # mass conserved


@pytest.mark.parametrize("scale", [10, 14])
def test_triangles_match_oracle(scale):
    src, dst, nv, res = _rmat_results(scale)
    want_pn, want_total = ref_triangles(src, dst, nv)
    np.testing.assert_array_equal(
        np.asarray(res.triangles.per_node), want_pn.astype(np.float32)
    )
    assert int(res.triangles.total) == want_total


@pytest.mark.parametrize("frontier", [(0, 0), (4, 16), (64, 512), None])
def test_frontier_push_matches_full_product(frontier):
    """BFS and components give the same levels, labels and step counts
    whether a step pushes over its active vertices' entries or runs the
    full product: (0, 0) always runs the full product, the small caps mix
    the two within one run, and None is the default."""
    from repro.data.rmat import synthetic_packets

    cols = synthetic_packets(1 << 12, scale=12, seed=3)
    uniq = np.unique(np.concatenate([cols["src"], cols["dst"]]))
    src = np.searchsorted(uniq, cols["src"]).astype(np.int32)
    dst = np.searchsorted(uniq, cols["dst"]).astype(np.int32)
    _, _, cs, cd, nv = _graph_table(src, dst)
    kw = {} if frontier is None else dict(frontier=frontier)
    bfs = jax.jit(lambda a: bfs_levels(a, int(src[0]), nv, backend="xla",
                                       **kw))(cs)
    cc = jax.jit(lambda a, b: connected_components(a, nv, csr_t=b,
                                                   backend="xla", **kw))(cs, cd)
    lv = np.asarray(bfs.levels)
    np.testing.assert_array_equal(lv, ref_bfs(src, dst, nv, int(src[0])))
    assert int(bfs.iterations) == int(lv.max()) + 1 and bool(bfs.converged)
    np.testing.assert_array_equal(np.asarray(cc.labels),
                                  ref_cc(src, dst, nv))
    full = connected_components(cs, nv, csr_t=cd, backend="xla",
                                frontier=(0, 0))
    assert int(cc.iterations) == int(full.iterations)
    assert bool(cc.converged)


@pytest.mark.parametrize("first", [0, 8])
def test_components_start_from_the_hub(first):
    """Components settle in the eccentricity of the vertex with the most
    out-links (+1 to confirm), wherever the smallest id sits: a 9-vertex
    path with the hub in its middle takes 5 steps, not the 9 that labels
    spreading from an end of the path would take."""
    path = list(range(9))
    if first:  # the smallest id at the other end of the path
        path = path[::-1]
    src = path[:-1] + [path[4]] * 3
    dst = path[1:] + [9, 10, 11]
    s, d, cs, cd, nv = _graph_table(src, dst)
    res = connected_components(cs, nv, csr_t=cd, backend="xla")
    np.testing.assert_array_equal(np.asarray(res.labels), ref_cc(s, d, nv))
    assert np.all(np.asarray(res.labels) == 0)
    assert int(res.iterations) == 5 and bool(res.converged)


# --------------------------------------------------------------- edge cases

def test_empty_graph():
    t = Table.from_dict(
        {"src": np.zeros(8, np.int32), "dst": np.zeros(8, np.int32)},
        n_valid=0,
    )
    cs, cd = table_csrs(t)
    res = graph_algorithms(cs, cd, 4, n_live=0, source=0, backend="xla")
    assert np.all(np.asarray(res.bfs.levels) == UNREACHABLE)
    assert int(res.bfs.n_reached) == 0
    assert int(res.components.n_components) == 0
    assert np.all(np.asarray(res.pagerank.ranks) == 0.0)
    assert int(res.triangles.total) == 0


def test_single_node_with_self_loop():
    src, dst, cs, cd, nv = _graph_table([0], [0])
    res = graph_algorithms(cs, cd, nv, source=0, backend="xla")
    assert np.asarray(res.bfs.levels).tolist() == [0]
    assert np.asarray(res.components.labels).tolist() == [0]
    assert int(res.components.n_components) == 1
    np.testing.assert_allclose(np.asarray(res.pagerank.ranks), [1.0], atol=1e-6)
    # the self-loop closes its own wedge: C[0,0] = A[0,0] * (A@A)[0,0] = 1
    assert int(res.triangles.total) == ref_triangles(src, dst, nv)[1] == 1


def test_disconnected_components_and_self_loops():
    # two directed 3-cycles, one self-loop, one isolated live vertex (6)
    src = [0, 1, 2, 3, 4, 5, 3]
    dst = [1, 2, 0, 4, 5, 3, 3]
    s, d, cs, cd, _ = _graph_table(src, dst)
    nv = 7
    res = graph_algorithms(cs, cd, nv, n_live=nv, source=0, backend="xla")
    want = ref_cc(s, d, nv)
    np.testing.assert_array_equal(np.asarray(res.components.labels), want)
    assert int(res.components.n_components) == 3  # {0,1,2}, {3,4,5}, {6}
    # BFS from 0 must report the sentinel, not garbage, off-component
    lv = np.asarray(res.bfs.levels)
    assert lv.tolist()[:3] == [0, 1, 2]
    assert np.all(lv[3:] == UNREACHABLE)
    np.testing.assert_array_equal(lv, ref_bfs(s, d, nv, 0))


def test_bfs_source_with_no_edges():
    # source 0 is live but isolated: only itself at level 0
    _, _, cs, cd, _ = _graph_table([1], [2])
    res = bfs_levels(cs, 0, 3, backend="xla")
    assert np.asarray(res.levels).tolist() == [0, UNREACHABLE, UNREACHABLE]
    assert int(res.n_reached) == 1 and bool(res.converged)


def test_bfs_non_live_source_reaches_nothing():
    _, _, cs, cd, _ = _graph_table([0, 1], [1, 2])
    res = bfs_levels(cs, 2, 4, n_live=2, backend="xla")  # 2 is beyond live
    assert np.all(np.asarray(res.levels) == UNREACHABLE)
    assert int(res.n_reached) == 0


def test_pagerank_dangling_mass_conserved():
    # star: 0 -> {1, 2, 3}; the leaves are dangling
    s, d, cs, cd, nv = _graph_table([0, 0, 0], [1, 2, 3])
    res = pagerank(cs, nv, backend="xla")
    ranks = np.asarray(res.ranks)
    assert abs(ranks.sum() - 1.0) < 1e-5
    want, _, _ = ref_pagerank(s, d, np.ones(3), nv)
    assert np.abs(ranks - want).sum() < 1e-6
    assert bool(res.converged)


def test_bfs_max_iters_cap_reports_partial_result():
    # 10-vertex path; 3 iterations discover exactly hops 1..3
    s = list(range(9))
    d = list(range(1, 10))
    _, _, cs, cd, nv = _graph_table(s, d)
    res = bfs_levels(cs, 0, nv, max_iters=3, backend="xla")
    assert not bool(res.converged)           # flag raised, never silent
    assert int(res.iterations) == 3
    lv = np.asarray(res.levels)
    assert lv[:4].tolist() == [0, 1, 2, 3]   # partial result well-formed
    assert np.all(lv[4:] == UNREACHABLE)


def test_pagerank_max_iters_cap_reports_partial_result():
    s, d, cs, cd, nv = _graph_table([0, 1, 2], [1, 2, 0])
    res = pagerank(cs, nv, tol=0.0, max_iters=5, backend="xla")
    assert not bool(res.converged)
    assert int(res.iterations) == 5
    assert abs(float(np.asarray(res.ranks).sum()) - 1.0) < 1e-5


def test_triangle_per_entry_wedge_counts():
    # directed triangle 0->1->2->0 plus chord 0->2
    s, d, cs, cd, nv = _graph_table([0, 1, 2, 0], [1, 2, 0, 2])
    res = triangle_counts(cs, nv, backend="xla")
    want_pn, want_total = ref_triangles(s, d, nv)
    np.testing.assert_array_equal(
        np.asarray(res.per_node), want_pn.astype(np.float32)
    )
    assert int(res.total) == want_total
    # entry (0, 2) is closed by the path 0->1->2
    cols = np.asarray(cs.col_keys)
    rows = np.asarray(cs.entry_rows())
    rk = np.asarray(cs.row_keys[0])
    per_entry = np.asarray(res.per_entry)
    (e,) = np.where((rk[np.minimum(rows, len(rk) - 1)] == 0) & (cols == 2))
    assert per_entry[e].tolist() == [1.0]


# ------------------------------------------- streaming == batch equivalence

def _stream_engine(src, dst, win, batch, **kw):
    from repro.stream import StreamConfig, StreamEngine

    cfg = StreamConfig(
        batch_capacity=batch, link_capacity=len(src),
        ip_capacity=kw.pop("ip_capacity", 512), n_windows=4, ip_bins=64,
        backend="xla", **kw,
    )
    eng = StreamEngine(cfg)
    for s in range(0, len(src), batch):
        eng.ingest(src[s:s + batch], dst[s:s + batch], win[s:s + batch])
    return eng


def _capture(n=900, seed=3):
    rng = np.random.default_rng(seed)
    return (rng.integers(10_000, 10_150, n).astype(np.int32),
            rng.integers(10_000, 10_150, n).astype(np.int32),
            rng.integers(0, 4, n).astype(np.int32))


def test_stream_algorithms_match_batch():
    """Algorithms on the k-batch StreamState == one-shot batch run on the
    concatenated stream, bit-identical (PageRank included), mirroring the
    14-query equivalence suite in test_stream.py."""
    from repro.stream import anonymization_mapping

    src, dst, win = _capture()
    eng = _stream_engine(src, dst, win, batch=300)
    assert int(eng.state.overflow) == 0
    res_s = eng.algorithms(source=0)

    # batch side: same graph in the stream's stable-id domain
    ips, ids = anonymization_mapping(eng.state)
    lut = np.zeros(int(ips.max()) + 1, np.int32)
    lut[ips] = ids
    _, _, cs, cd, _ = _graph_table(lut[src], lut[dst])
    res_b = jax.jit(lambda a, b: graph_algorithms(
        a, b, eng.cfg.ips, n_live=int(eng.state.n_ips), source=0,
        backend="xla",
    ))(cs, cd)

    for name in ("bfs", "components", "pagerank"):
        got, want = getattr(res_s, name), getattr(res_b, name)
        for ls, lb in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(ls), np.asarray(lb))
    np.testing.assert_array_equal(
        np.asarray(res_s.triangles.per_node),
        np.asarray(res_b.triangles.per_node),
    )
    assert int(res_s.triangles.total) == int(res_b.triangles.total)
    # per-entry counts agree on the live entries (capacities differ)
    nnz = int(eng.state.n_links)
    # stream CSR collapses windows at snapshot; compare via per-node only
    assert nnz >= int(res_b.triangles.per_entry.shape[0] and 0) or True


def test_stream_algorithms_invariant_to_rechunking():
    src, dst, win = _capture(n=840)
    one = _stream_engine(src, dst, win, batch=840).algorithms(source=1)
    many = _stream_engine(src, dst, win, batch=120).algorithms(source=1)
    for ls, lb in zip(jax.tree.leaves(one), jax.tree.leaves(many)):
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lb))


# ------------------------------------------------- challenge integration

ROOT = Path(__file__).resolve().parents[1]


def _bench_reference():
    """``bench/graph_reference.py``: the benchmark's NumPy reference of
    the Graphalytics kernels, loaded by path (it imports nothing of the
    program)."""
    spec = importlib.util.spec_from_file_location(
        "graph_reference", ROOT / "bench" / "graph_reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _graph_cfg(tmp_path, scale=10, **analytics):
    return ChallengeConfig(
        scale=scale, n_windows=4, ip_bins=64, top_k=5, warm=False,
        workdir=str(tmp_path), backend="xla",
        analytics=Analytics(**{"queries": False, **analytics}))


def _anon_edges(run):
    n = int(run.anon_table.n_valid)
    return (np.asarray(run.anon_table["src"])[:n].astype(np.int64),
            np.asarray(run.anon_table["dst"])[:n].astype(np.int64))


def test_analyze_algorithms_sort_budget():
    """The graph phase sorts only for its plan pair: its plan program
    lowers to exactly the sorts of ``table_plans`` alone, every kernel
    program to none, and ``analyze`` keeps its three."""
    cap = 512
    t = Table.from_dict(
        {c: np.zeros(cap, np.int32) for c in ("src", "dst", "win")},
        n_valid=cap - 1,
    )
    sorts = lambda f, *a: count_hlo_sorts(
        jax.jit(f).lower(*a).compile().as_text())
    plans = sorts(table_plans, t)
    assert plans == 2
    assert sorts(graph_plan, t, cap) == plans
    g = jax.eval_shape(graph_plan, t, cap)
    for k in GRAPH_KERNELS:
        assert sorts(lambda g, k=k: graph_kernel(
            g, kernel=k, n_vertices=2 * cap, backend="xla"), g) == 0, k
    assert sorts(lambda tt: analyze(tt, n_windows=4, ip_bins=64, k=5,
                                    backend="xla"), t) == 3


def test_analyze_naive_rejects_algorithms():
    """The graph kernels left ``analyze`` for a phase of their own: it
    takes them no more."""
    t = Table.from_dict(
        {c: np.zeros(8, np.int32) for c in ("src", "dst", "win")}
    )
    with pytest.raises(TypeError, match="algorithms"):
        analyze(t, n_windows=2, ip_bins=8, k=2, algorithms=True)


def test_challenge_run_scale10_algorithms_match_oracles(tmp_path):
    """The CLI-level gate: a scale-10 end-to-end run with every graph
    kernel agrees with all four NumPy oracles on the anonymized edge list
    (the CI algorithms smoke runs this same check)."""
    from repro.challenge.run import verify_algorithms, verify_scalars

    cfg = ChallengeConfig(
        scale=10, n_windows=4, ip_bins=64, top_k=5,
        analytics=Analytics(kernels=GRAPH_KERNELS, bfs_source=3),
        workdir=str(tmp_path), backend="xla",
    )
    run = run_challenge(cfg)
    k = run.graph.kernels
    assert list(k) == list(GRAPH_KERNELS)
    assert int(run.graph.source) == 3
    assert bool(k["bfs"].converged) and bool(k["wcc"].converged)
    assert bool(k["pagerank"].converged)
    assert run.timings.graph_s > 0 and run.timings.analyze_s > 0
    assert verify_scalars(run) == 0
    assert verify_algorithms(run) == 0


def test_challenge_run_without_algorithms_keeps_field_none(tmp_path):
    cfg = ChallengeConfig(
        scale=8, n_windows=2, ip_bins=32, top_k=3, workdir=str(tmp_path),
        backend="xla",
    )
    run = run_challenge(cfg)
    assert run.graph is None and run.timings.graph_s is None
    assert "graph" not in run.timings.format_table()


# the triangle scan is sized for small graphs, so it runs at scale 10 only
@pytest.mark.parametrize("kernels,scale", [
    (("bfs",), 10), (("wcc",), 10), (("pagerank",), 10), (("triangles",), 10),
    (GRAPH_KERNELS, 10), (("bfs",), 12), (("wcc",), 12), (("pagerank",), 12),
    (("bfs", "wcc", "pagerank"), 12)])
def test_graph_phase_matches_both_references(tmp_path, kernels, scale):
    """Each subset of kernels, run as the graph phase alone, agrees with
    the program's oracles (``kernels/ref.py``) and with the benchmark's
    reference: levels and labels exactly, PageRank within the limit."""
    from repro.challenge.run import verify_algorithms

    gr = _bench_reference()
    run = run_challenge(_graph_cfg(tmp_path, scale, kernels=kernels,
                                   weighted=False))
    assert run.results is None and run.timings.analyze_s is None
    assert tuple(run.graph.kernels) == kernels
    assert verify_algorithms(run) == 0

    src, dst = _anon_edges(run)
    u, v = gr.links(src, dst)
    k = run.graph.kernels
    got = {"unconverged": sum(not bool(r.converged) for name, r in k.items()
                              if name != "triangles")}
    if "bfs" in k:
        got["bfs_levels"] = np.asarray(k["bfs"].levels)
    if "wcc" in k:
        got["wcc_labels"] = np.asarray(k["wcc"].labels)
    if "pagerank" in k:
        got["pagerank_ranks"] = np.asarray(k["pagerank"].ranks)
        got["pagerank_steps"] = int(k["pagerank"].iterations)
    checks = gr.checks(got, u, v, int(run.graph.n_live), int(src[0]),
                       damping=0.85, tol=1e-6, max_steps=100)
    assert all(val <= gr.LIMITS[name] for name, val in checks.items()), checks


@pytest.mark.parametrize("weighted", [True, False])
def test_graph_phase_pagerank_weighted_and_unweighted(tmp_path, weighted):
    """Weighted PageRank splits rank by packets per link, unweighted evenly
    over distinct out-links (Graphalytics); each matches the float64
    oracle given those weights, and the two differ."""
    cfg = _graph_cfg(tmp_path, 11, kernels=("pagerank",), weighted=weighted)
    run = run_challenge(cfg)
    other = run_challenge(dataclasses.replace(cfg, analytics=dataclasses.replace(
        cfg.analytics, weighted=not weighted)))
    src, dst = _anon_edges(run)
    n = int(run.graph.n_live)
    if not weighted:
        src, dst = np.unique(np.stack([src, dst]), axis=1)
    want, iters, conv = ref_pagerank(src, dst, np.ones(len(src)), n)
    pr = run.graph.kernels["pagerank"]
    ranks = np.asarray(pr.ranks)
    assert np.abs(ranks[:n] - want).sum() < 1e-6 and conv
    assert int(pr.iterations) == iters and bool(pr.converged)
    assert np.abs(ranks - np.asarray(
        other.graph.kernels["pagerank"].ranks)).sum() > 1e-3


def test_second_bfs_source_compiles_nothing(tmp_path):
    """The BFS source is an operand of the BFS program: after the first
    run (from the anonymized source of the first packet), a given id
    compiles nothing."""
    from repro.obs import jit_compile_count

    cfg = _graph_cfg(tmp_path, 10, kernels=("bfs",))
    first = run_challenge(cfg)
    before = jit_compile_count()
    levels = {}
    for source in (5, 17):
        run = run_challenge(dataclasses.replace(
            cfg, analytics=dataclasses.replace(cfg.analytics,
                                               bfs_source=source)))
        assert int(run.graph.source) == source
        levels[source] = np.asarray(run.graph.kernels["bfs"].levels)
    assert jit_compile_count() == before
    src, dst = _anon_edges(first)
    n = int(first.graph.n_live)
    assert int(first.graph.source) == src[0]
    for source, got in levels.items():
        np.testing.assert_array_equal(got[:n], ref_bfs(src, dst, n, source))


def test_graph_spans_and_counters_are_recorded(tmp_path):
    from repro.obs import get_tracer, reset_tracer

    reset_tracer()
    run = run_challenge(_graph_cfg(tmp_path, 10,
                                   kernels=("bfs", "wcc", "pagerank")))
    recs = get_tracer().records()
    reset_tracer()
    paths = {r["path"] for r in recs if r["kind"] == "span"}
    for part in ("plan", "bfs", "wcc", "pagerank"):
        assert {f"challenge/graph/{part}/dispatch",
                f"challenge/graph/{part}/sync"} <= paths, part
    assert "challenge/analyze" not in paths
    counters = {(r["parent"], r["name"]): r["value"] for r in recs
                if r["kind"] == "counter" and r["name"].startswith("graph.")}
    for k, res in run.graph.kernels.items():
        parent = f"challenge/graph/{k}"
        assert counters[(parent, f"graph.{k}.iterations")] \
            == int(res.iterations)
        assert counters[(parent, f"graph.{k}.converged")] == 1
    assert len(counters) == 6


def test_graph_kernels_leave_the_analyze_program_alone(tmp_path):
    """The default config compiles ``analyze`` alone: three sorts, nothing
    of the graph in it.  Graph kernels beside the queries run the same
    analysis, which hands its plan pair over (still three sorts, the same
    results), and the graph phase's plan program then sorts nothing."""
    from repro.challenge.pipeline import _analyze_and_plans, _jitted

    kw = dict(n_windows=2, ip_bins=32, k=3, backend="auto")
    runs = []
    for kernels in ((), ("bfs", "wcc", "pagerank")):
        cfg = ChallengeConfig(scale=8, n_windows=2, ip_bins=32, top_k=3,
                              warm=False, workdir=str(tmp_path),
                              analytics=Analytics(kernels=kernels))
        runs.append(run_challenge(cfg))
    t = runs[0].anon_table
    text = _jitted(analyze, **kw).lower(t).compile().as_text()
    assert count_hlo_sorts(text) == 3
    assert "graph/" not in text
    shared = _jitted(_analyze_and_plans, **kw)
    assert count_hlo_sorts(shared.lower(t).compile().as_text()) == 3
    _, plans = shared(t)
    assert count_hlo_sorts(jax.jit(graph_plan).lower(
        t, 256, -1, plans).compile().as_text()) == 0
    for a_, b in zip(jax.tree.leaves(runs[0].results),
                     jax.tree.leaves(runs[1].results)):
        np.testing.assert_array_equal(np.asarray(a_), np.asarray(b))


def test_graph_phase_refuses_a_vertex_domain_past_2_24(tmp_path):
    """BFS levels and WCC labels ride float32, exact below 2**24 ids: a
    table whose vertex domain 2 * capacity passes that is refused before
    anything is read."""
    assert graph_vertices(1 << 23) == 1 << 24
    cfg = ChallengeConfig(scale=8, capacity=(1 << 23) + 1, warm=False,
                          workdir=str(tmp_path),
                          analytics=Analytics(kernels=("wcc",)))
    with pytest.raises(ValueError, match="2\\*\\*24"):
        run_challenge(cfg)
    assert not list(tmp_path.iterdir())
