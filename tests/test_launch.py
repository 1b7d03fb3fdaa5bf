"""Launch helpers: the per-device roofline peak table and the persistent
compilation cache's placement."""
import os

import pytest

from repro.launch import compile_cache
from repro.launch.roofline import DEVICE_PEAKS, peak_table, program_roofline


def test_peak_table_v5e_row():
    row = peak_table("TPU v5 lite")
    assert row["flops"] == 197e12 and row["bytes_per_s"] == 819e9
    assert row["device_kind"] == "TPU v5 lite"


def test_peak_table_cpu_row_is_named_explicitly():
    assert peak_table("cpu")["bytes_per_s"] == DEVICE_PEAKS["cpu"]["bytes_per_s"]


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "gpu", "tpu", ""])
def test_peak_table_unknown_kind_raises(kind):
    with pytest.raises(KeyError, match="no peak rates"):
        peak_table(kind)
    with pytest.raises(KeyError):
        program_roofline("", 1.0, kind)


class _Config:
    """A fresh stand-in for ``jax.config``: records updates, touches none."""

    def __init__(self):
        self.values = {}

    def update(self, name, value):
        self.values[name] = value


def test_compile_cache_respects_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cfg = _Config()
    assert compile_cache.use_compile_cache(cfg) == str(tmp_path)
    assert cfg.values == {}  # JAX reads the variable itself


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cfg = _Config()
    path = compile_cache.use_compile_cache(cfg)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert cfg.values == {"jax_compilation_cache_dir": path}
