"""Autotune lane + fused-epilogue gates (ISSUE 10).

Covers the tentpole's contracts end to end on CPU:

  * cache round-trip — a swept table reloads from disk and reproduces the
    chosen config through ``best_config`` with NO re-sweep (pure lookup);
  * shape-bucket keying — power-of-two buckets share entries, neighbours
    don't;
  * swept-config parity — every candidate the sweep may pick computes the
    same answer as the ref oracle (interpret mode);
  * fused-epilogue parity — the mask/retire kernel epilogues against the
    oracles;
  * the perf regression checker's gate/skip/record behavior (subprocess).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.defaults import DEFAULTS
from repro.kernels.histogram import histogram_pallas
from repro.kernels.ref import ref_histogram, ref_segmented_reduce
from repro.kernels.segreduce import segment_max_pallas

RNG = np.random.default_rng(7)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Point the autotuner at an isolated empty table directory."""
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    autotune.invalidate_cache()
    yield tmp_path
    autotune.invalidate_cache()


# ------------------------------------------------------------- table plumbing

def test_shape_bucket_powers_of_two():
    assert autotune.shape_bucket(1) == 1
    assert autotune.shape_bucket(2) == 2
    assert autotune.shape_bucket(3) == 4
    assert autotune.shape_bucket(1000) == 1024
    assert autotune.shape_bucket(1024) == 1024
    assert autotune.shape_bucket(1025) == 2048


def test_config_key_buckets_shapes_together():
    k_a = autotune.config_key("histogram", 1000, 500, "float32")
    k_b = autotune.config_key("histogram", 1024, 512, "float32")
    assert k_a == k_b == "histogram|n1024|s512|float32"
    assert autotune.config_key("histogram", 2049, 500, "float32") != k_a
    with pytest.raises(ValueError):
        autotune.config_key("nonsense", 10, 10, "float32")


def test_best_config_defaults_without_table(tune_dir):
    assert autotune.best_config("histogram", 999, 333, "float32") == \
        DEFAULTS["histogram"]
    assert autotune.best_config("cms", 10, 10, "int32") == DEFAULTS["cms"]


def test_best_config_reads_synthetic_table_without_sweeping(tune_dir):
    """Lookup is pure disk: a hand-written non-default entry comes back."""
    custom = {"block_rows": 256, "block_bins": 128}
    table = {
        "version": autotune.TABLE_VERSION,
        "backend": "cpu",
        "fingerprint": {},
        "entries": {
            autotune.config_key("histogram", 5000, 2000, "float32"): {
                "config": custom, "us": 1.0, "default_us": 2.0,
            }
        },
    }
    autotune.save_table(table, "cpu")
    # any shape in the same bucket hits; neighbours fall back to defaults
    assert autotune.best_config("histogram", 5000, 2000, "float32", "cpu") == custom
    assert autotune.best_config("histogram", 8192, 2048, "float32", "cpu") == custom
    assert autotune.best_config("histogram", 9000, 2048, "float32", "cpu") == \
        DEFAULTS["histogram"]


def test_best_config_rejects_malformed_entries(tune_dir):
    key = autotune.config_key("segreduce", 100, 100, "float32")
    for bad in ({"block_rows": 256}, {"block_rows": 0, "block_segs": 8},
                {"block_rows": "x", "block_segs": 8}, "junk", None):
        autotune.save_table({
            "version": autotune.TABLE_VERSION, "backend": "cpu",
            "fingerprint": {}, "entries": {key: {"config": bad}},
        }, "cpu")
        assert autotune.best_config("segreduce", 100, 100, "float32", "cpu") \
            == DEFAULTS["segreduce"]


def test_env_kill_switch_forces_defaults(tune_dir, monkeypatch):
    custom = {"block_rows": 256, "block_segs": 128}
    autotune.save_table({
        "version": autotune.TABLE_VERSION, "backend": "cpu",
        "fingerprint": {}, "entries": {
            autotune.config_key("segreduce", 64, 64, "float32"): {
                "config": custom, "us": 1.0, "default_us": 2.0}},
    }, "cpu")
    assert autotune.best_config("segreduce", 64, 64, "float32", "cpu") == custom
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert autotune.best_config("segreduce", 64, 64, "float32", "cpu") == \
        DEFAULTS["segreduce"]


def test_version_mismatch_degrades_to_defaults(tune_dir):
    autotune.save_table({"version": 999, "entries": {}}, "cpu")
    assert autotune.load_table("cpu") is None
    assert autotune.best_config("histogram", 10, 10, "float32", "cpu") == \
        DEFAULTS["histogram"]


def test_sweep_round_trip_reloads_same_config(tune_dir):
    """The tentpole acceptance: sweep -> persist -> reload -> same config,
    with the second read a pure table lookup (no sweep machinery)."""
    cands = [dict(DEFAULTS["histogram"]),
             {"block_rows": 256, "block_bins": 128}]
    entry = autotune.sweep_and_save(
        "histogram", 600, 300, "float32", backend="cpu", iters=1,
        candidates=cands,
    )
    assert entry["config"] in cands
    assert entry["us"] <= entry["default_us"]  # win-or-tie by construction
    autotune.invalidate_cache()
    assert autotune.best_config("histogram", 600, 300, "float32", "cpu") == \
        entry["config"]
    # same bucket, different raw shape -> same entry
    assert autotune.best_config("histogram", 1024, 512, "float32", "cpu") == \
        entry["config"]
    # and the on-disk JSON is the versioned table format
    table = json.loads((tune_dir / "cpu.json").read_text())
    assert table["version"] == autotune.TABLE_VERSION
    assert table["fingerprint"]["backend"]
    key = autotune.config_key("histogram", 600, 300, "float32")
    assert table["entries"][key]["config"] == entry["config"]


def test_candidate_lattice_default_first_and_vmem_guarded():
    for kernel in ("histogram", "segreduce", "cms"):
        cands = autotune.candidate_configs(kernel)
        assert cands[0] == DEFAULTS[kernel]
        assert len(cands) == len({tuple(sorted(c.items())) for c in cands})
        for c in cands:
            rows, width = sorted(c.values(), reverse=True)
            assert rows * width <= 1 << 20


# ----------------------------------------------- swept configs vs ref oracles

@pytest.mark.parametrize("config", autotune.candidate_configs("histogram"))
def test_histogram_candidates_match_oracle(config):
    ids = jnp.asarray(RNG.integers(-2, 902, 3000).astype(np.int32))
    w = jnp.asarray(RNG.random(3000).astype(np.float32))
    got = histogram_pallas(ids, 900, w, interpret=True, **config)
    want = ref_histogram(ids, 900, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("config", autotune.candidate_configs("segreduce"))
def test_segreduce_candidates_match_oracle(config):
    seg = jnp.asarray(RNG.integers(-2, 902, 3000).astype(np.int32))
    v = jnp.asarray(RNG.standard_normal(3000).astype(np.float32))
    got = segment_max_pallas(v, seg, 900, interpret=True, **config)
    want = ref_segmented_reduce(v, seg, 900, "max")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("config", autotune.candidate_configs("cms"))
def test_cms_candidates_match_oracle(config):
    from repro.kernels.ref import ref_cms_update
    from repro.kernels.sketch import cms_update_pallas

    depth, width, n = 3, 700, 2000
    counts = jnp.asarray(RNG.integers(0, 50, (depth, width)).astype(np.int32))
    ids = jnp.asarray(RNG.integers(-1, width, (depth, n)).astype(np.int32))
    props = jnp.asarray(RNG.integers(0, 99, n).astype(np.int32))
    got = cms_update_pallas(counts, ids, props, interpret=True, **config)
    want = ref_cms_update(counts, ids, props)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_dispatch_uses_table_config(tune_dir):
    """kernels/ops routes through best_config: a table pinning a custom
    block shape still computes the right answer on the interpret path."""
    from repro.kernels.ops import histogram as op_histogram

    autotune.save_table({
        "version": autotune.TABLE_VERSION, "backend": "cpu",
        "fingerprint": {}, "entries": {
            autotune.config_key("histogram", 2000, 600, "float32"): {
                "config": {"block_rows": 128, "block_bins": 256},
                "us": 1.0, "default_us": 2.0}},
    }, "cpu")
    ids = jnp.asarray(RNG.integers(0, 600, 2000).astype(np.int32))
    got = op_histogram(ids, 600, backend="interpret")
    np.testing.assert_allclose(
        np.asarray(got), np.bincount(np.asarray(ids), minlength=600))


# --------------------------------------------------- fused-epilogue contracts

def test_histogram_mask_retire_epilogue_matches_oracle():
    n, nb = 2500, 300
    ids = jnp.asarray(RNG.integers(0, nb, n).astype(np.int32))
    w = jnp.asarray(RNG.integers(1, 9, n).astype(np.int32))
    mask = jnp.asarray(RNG.integers(0, 2, nb).astype(bool))
    retire = float(np.iinfo(np.int32).min)
    got = histogram_pallas(ids, nb, w, valid_mask=mask, retire=retire,
                           interpret=True, block_rows=512, block_bins=64)
    want = ref_histogram(ids, nb, w, valid_mask=mask, retire=retire)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segmax_gate_mask_epilogues_match_oracle():
    n, ns = 2000, 250
    seg = jnp.asarray(RNG.integers(-1, ns + 1, n).astype(np.int32))
    v = jnp.asarray(RNG.standard_normal(n).astype(np.float32))
    mask = jnp.asarray(RNG.integers(0, 2, ns).astype(bool))
    init = jnp.asarray(RNG.standard_normal(ns).astype(np.float32))
    got = segment_max_pallas(
        v, seg, ns, init=init, valid_mask=mask, retire=-123.0,
        interpret=True, block_rows=256, block_segs=64)
    want = ref_segmented_reduce(
        v, seg, ns, "max", init, valid_mask=mask, retire=-123.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segmented_reduce_fused_interpret_matches_xla():
    n, ns = 1500, 200
    vals = jnp.asarray(RNG.integers(0, 1000, n).astype(np.int32))
    seg = jnp.asarray(RNG.integers(0, ns, n).astype(np.int32))
    mask = jnp.arange(ns) < 77
    imin = int(np.iinfo(np.int32).min)
    from repro.kernels.ops import segmented_reduce

    kw = dict(op="sum", valid_mask=mask, retire=imin)
    a = segmented_reduce(vals, seg, ns, backend="xla", **kw)
    b = segmented_reduce(vals, seg, ns, backend="interpret", **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- perf regression gate (CLI)

CHECKER = os.path.join(REPO, "tools", "check_perf_regression.py")
FP = {"backend": "cpu", "machine": "x", "cpu_count": 1, "cpu_model": "m"}


def _run_checker(*args):
    return subprocess.run([sys.executable, CHECKER, *args],
                          capture_output=True, text=True)


def _write(tmp_path, name, payload):
    p = tmp_path / name
    p.write_text(json.dumps(payload))
    return str(p)


def _queries_json(frac, fp=FP):
    return {"manifest": {"fingerprint": fp},
            "roofline": {"histogram": {"roofline_fraction": frac}}}


def _baseline_json(frac, fp=FP):
    return {"schema_version": 1, "fingerprint": fp,
            "roofline": {"histogram": frac},
            "latency": {"serve_p99_s": 0.01}}


def test_checker_passes_within_tolerance(tmp_path):
    cur = _write(tmp_path, "cur.json", _queries_json(0.6))
    base = _write(tmp_path, "base.json", _baseline_json(1.0))
    r = _run_checker("--kind", "roofline", "--current", cur,
                     "--baseline", base)
    assert r.returncode == 0, r.stdout + r.stderr


def test_checker_fails_on_regression(tmp_path):
    cur = _write(tmp_path, "cur.json", _queries_json(0.4))
    base = _write(tmp_path, "base.json", _baseline_json(1.0))
    r = _run_checker("--kind", "roofline", "--current", cur,
                     "--baseline", base)
    assert r.returncode == 1
    assert "REGRESSION" in r.stderr


def test_checker_skips_on_foreign_hardware(tmp_path):
    other = dict(FP, cpu_model="other box")
    cur = _write(tmp_path, "cur.json", _queries_json(0.0001, fp=other))
    base = _write(tmp_path, "base.json", _baseline_json(1.0))
    r = _run_checker("--kind", "roofline", "--current", cur,
                     "--baseline", base)
    assert r.returncode == 0
    assert "skipping" in r.stdout


def test_checker_skips_without_baseline(tmp_path):
    cur = _write(tmp_path, "cur.json", _queries_json(0.0001))
    r = _run_checker("--kind", "roofline", "--current", cur,
                     "--baseline", str(tmp_path / "missing.json"))
    assert r.returncode == 0


def test_checker_latency_gate(tmp_path):
    base = _write(tmp_path, "base.json", _baseline_json(1.0))
    serve_ok = {"manifest": {"fingerprint": FP},
                "runs": {"baseline": {"batch_latency": {"p99_s": 0.02}}}}
    cur = _write(tmp_path, "serve.json", serve_ok)
    assert _run_checker("--kind", "latency", "--current", cur,
                        "--baseline", base).returncode == 0
    serve_bad = {"manifest": {"fingerprint": FP},
                 "runs": {"baseline": {"batch_latency": {"p99_s": 0.2}}}}
    cur = _write(tmp_path, "serve_bad.json", serve_bad)
    r = _run_checker("--kind", "latency", "--current", cur,
                     "--baseline", base)
    assert r.returncode == 1 and "REGRESSION" in r.stderr


def test_checker_write_baseline_round_trip(tmp_path):
    queries = {"manifest": {"fingerprint": FP},
               "roofline": {k: {"roofline_fraction": 1.5}
                            for k in ("histogram", "segmented_reduce",
                                      "cms_update", "all14_pipeline")}}
    serve = {"manifest": {"fingerprint": FP},
             "runs": {"baseline": {"batch_latency": {"p99_s": 0.005}}}}
    q = _write(tmp_path, "q.json", queries)
    s = _write(tmp_path, "s.json", serve)
    out = str(tmp_path / "baseline.json")
    assert _run_checker("--write-baseline", "--queries", q, "--serve", s,
                        "--out", out).returncode == 0
    assert _run_checker("--kind", "roofline", "--current", q,
                        "--baseline", out).returncode == 0
    assert _run_checker("--kind", "latency", "--current", s,
                        "--baseline", out).returncode == 0
