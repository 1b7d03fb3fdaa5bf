"""The benchmark's layout: BENCHMARK.json, and every file found by name."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import CELLS, ROOT, benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    from bench import harness

    return harness


def test_benchmark_json_keeps_to_its_shape():
    bm = benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"] == ["python3", "bench/run.py"]
    assert all((ROOT / p).is_dir() for p in bm["paths"])
    assert 1 <= bm["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    # a full check of 24 cells fits in 43200 s
    assert (2 + 14 * 24) * (bm["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    h = _bench()
    entry = {w["name"]: w for w in benchmark()["workloads"]}[cell]
    work = h.load_json("workloads", cell)
    assert (work["config"], work["traffic"]) == (entry["config"],
                                                 entry["traffic"])
    cfg = h.load_json("configs", work["config"])
    assert cfg["name"] == work["config"]
    traffic = h.load_json("traffic", work["traffic"])
    assert traffic["generator"] == "rmat"
    assert callable(h.load_module("drivers", work["driver"]).run)


def test_configs_match_their_files():
    for c in benchmark()["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"])
        assert c["file"].startswith("bench/configs/")


@pytest.mark.parametrize("metric", [m["name"] for m in benchmark()["per_layer"]])
def test_per_layer_readers_are_found_and_cells_report_what_they_move(metric):
    h = _bench()
    bm = benchmark()
    m = {x["name"]: x for x in bm["per_layer"]}[metric]
    assert callable(h.load_module("metrics", metric).read)
    assert h.load_module("metrics", metric).read({"spans": {},
                                                 "trace": None}) is None
    for cell in m["workloads"]:
        e2e, layer = h.cell_metrics(bm, cell)
        assert m["moves"] in {x["name"] for x in e2e}
        assert metric in {x["name"] for x in layer}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_e2e_and_a_layer_metric(cell):
    e2e, layer = _bench().cell_metrics(benchmark(), cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and layer


def test_a_cell_added_as_new_files_is_picked_up(tmp_path):
    h = _bench()
    root = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", root,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "traffic" / "rmat16-burst.json").write_text(json.dumps(
        {"generator": "rmat", "n_packets": 65536, "scale": 16, "a": 0.6,
         "b": 0.15, "c": 0.15, "row_group_size": 8192}))
    (root / "configs" / "service-small.json").write_text(json.dumps(
        {"name": "service-small", "tier": "exact", "batch_capacity": 8192,
         "link_capacity": 131072, "ip_capacity": 262144, "n_windows": 4,
         "ip_bins": 256, "top_k": 5, "backend": "auto", "reduced": []}))
    (root / "workloads" / "service-small-burst.json").write_text(json.dumps(
        {"config": "service-small", "traffic": "rmat16-burst",
         "driver": "service"}))
    (root / "metrics" / "fold_batches.py").write_text(
        "def read(obs):\n    d = obs['spans'].get('fold.exact')\n"
        "    return len(d) if d else None\n")
    bm = benchmark()
    bm["workloads"].append({"name": "service-small-burst",
                            "config": "service-small",
                            "traffic": "rmat16-burst", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "fold_batches", "unit": "batches",
                            "better": "higher", "source": "host_clock",
                            "layer": "stream fold", "moves":
                            "ingest_packets_per_s",
                            "workloads": ["service-small-burst"]})
    for m in bm["end_to_end"]:
        if m["name"] == "ingest_packets_per_s":
            m["workloads"].append("service-small-burst")
    work = h.load_json("workloads", "service-small-burst", root)
    assert h.load_json("configs", work["config"], root)["link_capacity"] \
        == 131072
    assert h.load_json("traffic", work["traffic"], root)["scale"] == 16
    e2e, layer = h.cell_metrics(bm, "service-small-burst")
    assert {"setup_s", "ingest_packets_per_s"} <= {m["name"] for m in e2e}
    assert [m["name"] for m in layer] == ["fold_batches"]
    reader = h.load_module("metrics", "fold_batches", root)
    assert reader.read({"spans": {"fold.exact": [0.1, 0.2]}}) == 2
    # nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())


def _run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "batch-rmat22",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_command_refuses_a_machine_without_tpu():
    proc = _run_command(ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    bm = benchmark()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = _run_command(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)


def test_compile_counter_counts_requests_until_closed():
    import jax
    import jax.numpy as jnp

    counter = _bench().CompileCounter()
    try:
        jax.jit(lambda x: x * 3 + 2)(jnp.arange(7)).block_until_ready()
        assert counter.read()[0] >= 1
    finally:
        counter.close()
    before = counter.read()
    jax.jit(lambda x: x * 5 - 1)(jnp.arange(9)).block_until_ready()
    assert counter.read() == before
