"""Device scopes, profiler annotations and JIT counters of the program.

Scopes are read from the ``op_name`` metadata of each program compiled for
the CPU (the name stack JAX writes beside every HLO instruction); Pallas
kernels run in interpret mode there, and their ``name=`` becomes a scope
of the ops that emulate them.  The annotations and counters are read from a
CPU profiler trace and the ``repro.obs`` tracer.
"""
import dataclasses
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.challenge.pipeline import analyze
from repro.core.anonymize import anonymize
from repro.core.sketch import SketchConfig, init_sketch, update_sketch
from repro.core.table import Table
from repro.obs import get_tracer, jit_compile_count, reset_tracer, span
from repro.stream import StreamConfig, StreamEngine
from repro.stream.engine import stream_plq, update_state
from repro.stream.state import init_state

jax.config.update("jax_platform_name", "cpu")

N = 512


@pytest.fixture(autouse=True)
def _fresh_tracer():
    reset_tracer()
    yield
    reset_tracer()


def _table(n=N, n_windows=4):
    rng = np.random.default_rng(0)
    return Table.from_dict({
        "src": rng.integers(0, 97, n).astype(np.int32),
        "dst": rng.integers(0, 53, n).astype(np.int32),
        "win": rng.integers(0, n_windows, n).astype(np.int32),
    }, n_valid=n - 5)


def _op_names(fn, *args):
    """Every ``op_name`` of ``fn``'s program compiled for the CPU, without
    its ``jit(...)`` parts."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return {"/".join(p for p in name.split("/") if "(" not in p)
            for name in re.findall(r'op_name="([^"]+)"', text)}


def _has_scope(names, path):
    return any(n.startswith(path + "/") for n in names)


ANALYZE = ["plan", "scalars", "groups", "topk", "windowed", "activity",
           "overlap"]


@pytest.fixture(scope="module")
def analyze_names():
    return _op_names(lambda t: analyze(t, n_windows=4, ip_bins=64, k=5,
                                       backend="interpret"), _table())


@pytest.mark.parametrize("scope", ANALYZE)
def test_analyze_names_each_query_family(analyze_names, scope):
    assert _has_scope(analyze_names, f"analyze/{scope}")


def test_analyze_names_the_histogram_kernel(analyze_names):
    assert _has_scope(analyze_names, "analyze/activity/histogram")


def test_analyze_names_the_algorithm_pass_when_on():
    names = _op_names(lambda t: analyze(t, n_windows=4, ip_bins=64, k=5,
                                        backend="xla", algorithms=True),
                      _table(256))
    assert _has_scope(names, "analyze/algorithms")


def test_naive_analyze_uses_the_same_family_names():
    names = _op_names(lambda t: analyze(t, n_windows=4, ip_bins=64, k=5,
                                        backend="xla", use_plan=False),
                      _table(256))
    for scope in ("scalars", "groups", "topk", "windowed", "activity",
                  "overlap"):
        assert _has_scope(names, f"analyze/{scope}"), scope


@pytest.mark.parametrize("scope", ["unique", "permutation", "factorize",
                                   "gather"])
def test_anonymize_names_each_step(scope):
    names = _op_names(lambda t, k: anonymize(t, k).table, _table(),
                      jax.random.key(0))
    assert _has_scope(names, f"anonymize/{scope}")


@pytest.mark.parametrize("scope", ["dictionary", "links", "activity"])
def test_update_state_names_each_part(scope):
    state = init_state(2 * N, 4 * N, 4, 64)
    cols = [jnp.asarray(np.random.default_rng(1).integers(0, 97, N),
                        jnp.int32) for _ in range(2)]
    win = jnp.zeros((N,), jnp.int32)
    names = _op_names(
        lambda s, a, b, w: update_state(s, a, b, w, N - 3, backend="xla"),
        state, *cols, win)
    assert _has_scope(names, f"update_state/{scope}")


@pytest.fixture(scope="module")
def sketch_names():
    state = init_sketch(SketchConfig(cms_depth=2, cms_width=256, hll_p=6,
                                     heavy_capacity=8))
    src, dst = (jnp.asarray(np.random.default_rng(s).integers(0, 97, N),
                            jnp.int32) for s in (2, 3))
    return _op_names(
        lambda st, a, b: update_sketch(st, a, b, N - 3, backend="interpret"),
        state, src, dst)


@pytest.mark.parametrize("scope", ["groupby", "cms", "hll", "heavy"])
def test_update_sketch_names_each_summary(sketch_names, scope):
    assert _has_scope(sketch_names, f"update_sketch/{scope}")


@pytest.mark.parametrize("path", ["update_sketch/cms/cms_update",
                                  "update_sketch/hll/segmax"])
def test_update_sketch_names_its_kernels(sketch_names, path):
    assert _has_scope(sketch_names, path)


# ------------------------------------------------------- the JIT counters

def test_fresh_jit_counts_trace_lower_and_compile_under_its_span():
    f = jax.jit(lambda x: x * 7 + 1)
    x = jnp.arange(11)
    with span("outer"):
        with span("inner"):
            f(x).block_until_ready()
    jit = [r for r in get_tracer().records() if r["kind"] == "counter"]
    names = {r["name"] for r in jit}
    assert {"jit.trace_s", "jit.lower_s", "jit.compile_s"} <= names
    assert all(r["parent"] == "outer/inner" for r in jit)
    assert all(r["value"] >= 0 for r in jit)

    reset_tracer()
    with span("again"):
        f(x).block_until_ready()
    assert not [r for r in get_tracer().records() if r["kind"] == "counter"]


def test_jit_compile_count_moves_only_on_a_compile():
    f = jax.jit(lambda x: x - 3)
    before = jit_compile_count()
    f(jnp.arange(5)).block_until_ready()
    after = jit_compile_count()
    assert after > before
    f(jnp.arange(5)).block_until_ready()
    assert jit_compile_count() == after


def test_stream_plq_marks_only_batches_that_compiled(tmp_path):
    from repro.data.plq import write_plq
    from repro.data.rmat import synthetic_packets
    from repro.challenge.pipeline import window_column

    cols = synthetic_packets(1024, scale=10, seed=5)
    path = str(tmp_path / "c.plq")
    write_plq(path, cols, row_group_size=256)
    win = window_column(cols["ts"], 3)
    # a capacity no other test compiles, so the first pass compiles here
    engine = StreamEngine(StreamConfig(batch_capacity=264,
                                       link_capacity=2056, n_windows=3,
                                       ip_bins=64, top_k=5, backend="xla"))
    first = stream_plq(engine, path, win)
    second = stream_plq(engine, path, win)
    assert first[0].compile
    assert not any(t.compile for t in second)
    passes = [r for r in get_tracer().records()
              if r["kind"] == "span" and r["name"] == "stream.pass"]
    assert len(passes) == 2 and passes[0]["parent"] is None


def test_snapshot_spans_name_each_tier_and_sketch_part():
    engine = StreamEngine(StreamConfig(
        batch_capacity=128, link_capacity=512, n_windows=2, ip_bins=32,
        top_k=4, backend="xla", tier="both",
        sketch=SketchConfig(cms_depth=2, cms_width=128, hll_p=5,
                            heavy_capacity=8)))
    rng = np.random.default_rng(4)
    engine.ingest(rng.integers(0, 50, 100), rng.integers(0, 50, 100),
                  rng.integers(0, 2, 100))
    engine.snapshot()
    paths = {r["path"] for r in get_tracer().records()
             if r["kind"] == "span"}
    assert {"snapshot", "snapshot/exact", "snapshot/sketch",
            "snapshot/sketch/scalars", "snapshot/sketch/heavy_links",
            "snapshot/sketch/heavy_talkers",
            "snapshot/sketch/bounds"} <= paths


def test_run_challenge_phases_have_dispatch_and_sync(tmp_path):
    from repro.challenge import ChallengeConfig, run_challenge

    run_challenge(ChallengeConfig(scale=8, n_packets=256, warm=False,
                                  workdir=str(tmp_path)))
    paths = {r["path"] for r in get_tracer().records()
             if r["kind"] == "span"}
    for phase in ("build_device", "anonymize", "analyze"):
        assert {f"challenge/{phase}/dispatch",
                f"challenge/{phase}/sync"} <= paths


def test_run_challenge_builds_its_programs_once(tmp_path):
    from repro.challenge import ChallengeConfig, run_challenge

    cfg = ChallengeConfig(scale=8, n_packets=256, warm=False,
                          workdir=str(tmp_path))
    first = run_challenge(cfg)
    reset_tracer()
    before = jit_compile_count()
    second = run_challenge(cfg)
    assert jit_compile_count() == before
    assert not [r for r in get_tracer().records()
                if r["kind"] == "counter" and r["name"].startswith("jit.")
                and (r["parent"] or "").startswith("challenge")]
    for f in dataclasses.fields(first.results):
        a, b = getattr(first.results, f.name), getattr(second.results, f.name)
        assert jax.tree.structure(a) == jax.tree.structure(b), f.name
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f.name)


# ------------------------------------------------- profiler annotations

def test_spans_annotate_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("outer"):
            with span("inner"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    names = [e.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events]
    assert names.count("repro.outer") == 1
    assert names.count("repro.outer/inner") == 1
