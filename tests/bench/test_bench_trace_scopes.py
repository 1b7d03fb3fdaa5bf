"""Device time by program scope and idle time by program span
(``bench/trace_scopes.py``), and the readers of the program's JIT counters
and snapshot spans, on the v5e fixture, a CPU trace and made-up records."""
import glob
import time
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench import harness, trace_reduce, trace_scopes

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_sort.xplane.pb"


@pytest.mark.parametrize("op_name, scope", [
    ("jit(run)/analyze/windowed/while:", "analyze/windowed"),
    ("jit(run)/analyze/windowed/while/body/groups/add:",
     "analyze/windowed/groups"),
    ("jit(f)/update_state/links/cond/branch_1_fun/sort", "update_state/links"),
    ("jit(f)/update_sketch/cms/cms_update/pallas_call:custom",
     "update_sketch/cms/cms_update"),
    # below a nested call JAX repeats the names open where the callee was
    # first traced, here another scope's
    ("jit(<unknown>)/analyze/overlap/jit(searchsorted)/jit(<unknown>)/"
     "link_table/jit(searchsorted)/vmap()/while/body/closed_call/gather:",
     "analyze/overlap"),
    ("jit(<unknown>)/analyze/topk/while/body/closed_call/"
     "argmax_top_k.<locals>.body/reduce:", "analyze/topk"),
    ("jit(<lambda>)/jit(sort)/sort:", ""),
    ("jit(<lambda>)/add:", ""),
    ("", ""),
])
def test_scope_of_keeps_the_program_scopes(op_name, scope):
    assert trace_scopes.scope_of(op_name) == scope


def test_chip_ops_reads_each_event_and_its_tf_op_stat():
    (plane, events, ops), = trace_scopes.chip_ops(FIXTURE.read_bytes())
    assert plane == "/device:TPU:0"
    by_name = {trace_reduce.op_name(text): op for text, op in ops.values()}
    assert by_name["%sort.6"] == "jit(<lambda>)/jit(sort)/sort:"
    assert by_name["%multiply_add_fusion"] == "jit(<lambda>)/add:"
    # the same intervals ProfileData gives, each keyed by its metadata id
    (chip,) = trace_reduce.device_ops(trace_reduce.load(str(FIXTURE)))
    assert len(events) == len(chip)
    assert sorted(s for s, _, _ in events) == pytest.approx(
        sorted(s for s, _, _ in chip), abs=1)
    assert {trace_reduce.op_name(ops[m][0]) for _, _, m in events} == {
        trace_reduce.op_name(n) for _, _, n in chip}


def test_a_loop_takes_the_scope_its_body_shares():
    ops = [(0, 10, "loop"), (0, 2, "a"), (3, 5, "b"), (11, 12, "c"),
           (20, 30, "mixed"), (21, 22, "d"), (23, 24, "e")]
    scope = {"a": "analyze/windowed/groups", "b": "analyze/windowed",
             "c": "analyze/topk", "d": "link_table", "e": "analyze/overlap"}
    out = trace_scopes.infer_scopes(ops, scope)
    assert out["loop"] == "analyze/windowed"
    assert out["mixed"] == ""
    assert {k: out[k] for k in scope} == scope


def test_device_scopes_takes_the_union_of_nested_intervals():
    # a loop (0-10) whose body ops (1-3, 2-4, 6-8) lie inside it, one op
    # of another family (12-14), one op with no scope (15-20)
    ops = [(0, 10, "loop"), (1, 3, "a"), (2, 4, "b"), (6, 8, "c"),
           (12, 14, "d"), (15, 20, "e")]
    scope = {"loop": "analyze/windowed", "a": "analyze/windowed",
             "b": "analyze/windowed/groups", "c": "analyze/windowed/groups",
             "d": "analyze/topk", "e": ""}
    per, scoped = trace_scopes.device_scopes([[(s * 1e9, e * 1e9, n)
                                               for s, e, n in ops]],
                                             [scope], 0, 18e9)
    assert per == pytest.approx({"analyze": 12.0, "analyze/windowed": 10.0,
                                 "analyze/windowed/groups": 4.0,
                                 "analyze/topk": 2.0})
    assert scoped == pytest.approx(12.0)


def test_device_scopes_averages_over_chips():
    chip = [(0, 2e9, "x")]
    per, scoped = trace_scopes.device_scopes([chip, []], [{"x": "s"}, {}],
                                             0, 10e9)
    assert per == {"s": 1.0} and scoped == 1.0


def test_reduce_keeps_the_summary_and_adds_scopes():
    summary = trace_scopes.reduce(str(FIXTURE))
    plain = trace_reduce.reduce(trace_reduce.load(str(FIXTURE)))
    assert {k: summary[k] for k in plain} == plain
    # the fixture's program has no scopes of its own
    assert summary["device_scopes"] == [] and summary["scoped_share"] == 0
    assert [op for op, _, _ in summary["scoped_ops"]][0] == "%sort.6"
    assert dict(summary["idle_spans"]) == dict(plain["idle_gaps"])
    assert summary["idle_program_share"] == 0


def test_idle_time_goes_to_the_program_span_that_holds_it(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    from repro.obs import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(trace_reduce.WINDOW):
            with span("serve"):
                with span("wait"):
                    time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    summary = trace_scopes.reduce(path)
    idle = dict(summary["idle_spans"])
    # the CPU trace has no chip plane: the whole window is idle
    assert idle["repro.serve/wait"] >= 0.05
    assert idle["repro.serve/wait"] == max(idle.values())
    assert summary["idle_program_share"] > 0.5


# ----------------------------------------------------------------- readers

def _records(*recs):
    return lambda: type("T", (), {"records": lambda self: list(recs)})()


def _span(name, seq, t, d, parent=None):
    return {"kind": "span", "name": name, "seq": seq, "t_mono": t,
            "duration_s": d, "parent": parent}


def _jit(name, seq, end, secs, parent="challenge/analyze/dispatch"):
    return {"kind": "counter", "name": name, "seq": seq, "t_mono": end,
            "value": secs, "parent": parent}


def test_dispatch_reader_sums_each_window_pass(monkeypatch):
    import repro.obs

    recs = [
        _span("challenge", 0, 0.0, 10.0), _jit("jit.compile_s", 1, 5.0, 4.0),
        # window pass 1: a trace of 1 s holding a nested one, a lower
        _span("challenge", 2, 20.0, 10.0),
        _jit("jit.trace_s", 3, 21.5, 0.5), _jit("jit.trace_s", 4, 22.0, 1.5),
        _jit("jit.lower_s", 5, 23.0, 0.5),
        # window pass 2: one compile request
        _span("challenge", 6, 40.0, 10.0), _jit("jit.compile_s", 7, 41.0, 0.25),
        # the traced pass
        _span("challenge", 8, 60.0, 10.0), _jit("jit.trace_s", 9, 61.0, 0.75),
    ]
    monkeypatch.setattr(repro.obs, "get_tracer", _records(*recs))
    reader = harness.load_module("metrics", "dispatch_s.batch")
    obs = {"spans": {}, "trace": {"busy_s": 1.0}, "window": {"passes": 2}}
    assert reader.read(obs) == pytest.approx((2.0 + 0.25) / 2)
    # untraced: the last two passes are the window's
    untraced = dict(obs, trace=None)
    assert reader.read(untraced) == pytest.approx((0.25 + 0.75) / 2)


def test_dispatch_reader_is_silent_without_counters(monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "get_tracer", _records(
        _span("challenge", 0, 0.0, 1.0), _span("challenge", 1, 2.0, 1.0)))
    reader = harness.load_module("metrics", "dispatch_s.batch")
    assert reader.read({"spans": {}, "trace": None,
                        "window": {"passes": 1}}) is None
    assert reader.read({"spans": {}, "trace": None}) is None


def test_sketch_snapshot_reader_means_the_window_snapshots(monkeypatch):
    import repro.obs

    recs = [_span("sketch", i, float(i), w, parent="snapshot")
            for i, w in enumerate([9.0, 0.2, 0.4, 5.0])]
    recs.append(_span("sketch", 9, 9.0, 7.0, parent="other"))
    monkeypatch.setattr(repro.obs, "get_tracer", _records(*recs))
    reader = harness.load_module("metrics", "sketch_snapshot_s")
    obs = {"spans": {}, "trace": {"busy_s": 1.0}, "window": {"passes": 2}}
    assert reader.read(obs) == pytest.approx(0.3)
    assert reader.read(dict(obs, window={"passes": 4})) is None


def test_sketch_snapshot_reader_is_silent_without_spans(monkeypatch):
    import repro.obs

    monkeypatch.setattr(repro.obs, "get_tracer", _records())
    reader = harness.load_module("metrics", "sketch_snapshot_s")
    assert reader.read({"spans": {}, "trace": None,
                        "window": {"passes": 3}}) is None
