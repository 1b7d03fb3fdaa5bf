"""Each window driver at a tiny size on the CPU: the loop, the arithmetic of
its rates, and a sound run that comes out correct."""
import pytest

from bench_tiny import CELLS, run_driver, run_tiny, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


def test_batch_window_loop_and_rate(root):
    ctx, res = run_driver(root, "batch-rmat22", seconds=0.5)
    w = res.obs["window"]
    assert w["wall_s"] >= 0.5 and w["passes"] >= 1
    assert w["packets"] == w["passes"] * ctx.traffic["n_packets"]
    assert res.attempted == w["passes"]
    assert res.e2e["challenge_packets_per_s"] == w["packets"] / w["wall_s"]
    assert 0 < res.e2e["setup_s"] == ctx.setup_s
    # one span per phase per pass of the window
    assert all(len(res.obs["spans"][p]) == w["passes"]
               for p in ("read", "build_host", "build_device", "anonymize",
                         "analyze"))
    assert all(v == 0 for v, _ in res.checks.values())


@pytest.mark.parametrize("cell", CELLS[1:])
def test_service_window_loop_and_rates(root, cell):
    ctx, res = run_driver(root, cell, seconds=0.5)
    w = res.obs["window"]
    per_pass = ctx.traffic["n_packets"]
    assert w["wall_s"] >= 0.5 and w["passes"] >= 1
    assert w["packets"] == w["passes"] * per_pass
    rate = {"exact": "ingest_packets_per_s",
            "sketch": "ingest_packets_per_s.sketch"}[ctx.config["tier"]]
    assert res.e2e[rate] == w["packets"] / w["wall_s"]
    assert res.e2e["snapshot_s"] == sum(w["snapshot_walls"]) / w["passes"]
    assert sum(w["snapshot_walls"]) < w["wall_s"]
    tier = ctx.config["tier"]
    batches = w["passes"] * (per_pass // ctx.traffic["row_group_size"])
    assert len(res.obs["spans"][f"fold.{tier}"]) == batches
    assert len(res.obs["spans"]["batch_prep"]) == batches
    assert all(v <= lim for v, lim in res.checks.values())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct_and_reports_its_metrics(root, cell, trace):
    from bench import harness

    line = run_tiny(root, cell, trace=trace)
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "checks"
    e2e, layer = harness.cell_metrics(harness.load_benchmark(), cell)
    if trace:
        # the CPU has no device plane, so only the idle shares stay silent
        want = {m["name"] for m in layer
                if not m["name"].startswith("device_idle_share")}
        assert set(line["metrics"]) == want
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert line["device"]["window_s"] > 0
    else:
        assert set(line["metrics"]) == {m["name"] for m in e2e}
    assert line["device"]["platform"] == "cpu"
