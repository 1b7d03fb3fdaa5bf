"""The trace reduction, on a trace recorded on a TPU v5e and on made-up
intervals.

``fixtures/v5e_sort.xplane.pb`` was written by ``fixtures/record_trace.py``
on one chip: inside ``bench.window``, three jitted sorts of 2^20 int32
(``bench.step``), each followed by 50 ms of host sleep
(``bench.host_wait``).
"""
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench import trace_reduce

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_sort.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(trace_reduce.load(str(FIXTURE)))


def test_fixture_has_one_chip_and_the_annotations():
    pd = trace_reduce.load(str(FIXTURE))
    chips = trace_reduce.device_ops(pd)
    assert len(chips) == 1 and chips[0]
    names = [sp[2] for sp in trace_reduce.host_spans(pd)]
    assert names.count("bench.window") == 1
    assert names.count("bench.step") == 3
    assert names.count("bench.host_wait") == 3


def test_busy_and_idle_add_up_to_the_window(summary):
    assert 0.15 < summary["window_s"] < 2.0
    assert 0 < summary["busy_s"] < summary["window_s"]
    idle = sum(s for _, s in summary["idle_gaps"])
    assert idle == pytest.approx(summary["window_s"] - summary["busy_s"],
                                 rel=1e-6)
    assert summary["idle_share"] == pytest.approx(
        1 - summary["busy_s"] / summary["window_s"])


def test_idle_gaps_are_named_by_what_the_host_did(summary):
    gaps = dict(summary["idle_gaps"])
    # three 50 ms sleeps, while the device had nothing to do
    assert 0.15 <= gaps["bench.host_wait"] < 0.2
    assert gaps["bench.host_wait"] == max(gaps.values())


def test_device_ops_are_short_names_with_device_time(summary):
    ops = summary["device_ops"]
    assert 0 < len(ops) <= 10
    assert all(" = " not in name and name.startswith("%") for name, _ in ops)
    assert sum(s for _, s in ops) <= summary["busy_s"] * 1.0001


def _iv(*pairs):
    return [(s, e, "op") for s, e in pairs]


def test_merge_clips_and_unions():
    assert trace_reduce.merge(_iv((0, 5), (3, 8), (10, 12), (11, 20)),
                              2, 15) == [(2, 8), (10, 15)]
    assert trace_reduce.merge([], 0, 1) == []


def test_gaps_are_the_complement_inside_the_window():
    assert trace_reduce.gaps([(2, 8), (10, 15)], 0, 20) == [
        (0, 2), (8, 10), (15, 20)]
    assert trace_reduce.gaps([], 0, 3) == [(0, 3)]


def test_op_name_keeps_the_instruction_name():
    assert trace_reduce.op_name("%fusion.12 = s32[8]{0} fusion(...)") \
        == "%fusion.12"
