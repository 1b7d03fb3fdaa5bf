"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed with jaxlib, so each kernel is lowered and
compiled for a v5e that is described, not attached: nothing runs, but the
Mosaic lowering refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, too much VMEM).  Shapes are the widths the main path uses:
2^24-row reductions into 4096 outputs (the largest count ``backend="auto"``
still sends to Pallas), the sketch tier's default 4 x 4096 Count-Min folded
2^15 proposals at a time, and its 2^12 HyperLogLog registers.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.histogram import histogram_pallas
from repro.kernels.segreduce import segment_max_pallas
from repro.kernels.sketch import cms_update_pallas, hll_update_pallas

ROWS = 1 << 24
OUTS = 4096
CMS_DEPTH, CMS_WIDTH, CMS_BATCH = 4, 4096, 1 << 15
HLL_REGISTERS = 1 << 12


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("epilogue", [False, True])
def test_histogram_compiles(spec, epilogue):
    def fn(ids, w, gate, init, mask):
        if not epilogue:
            return histogram_pallas(ids, OUTS, w)
        return histogram_pallas(ids, OUTS, w, init=init, gate_ids=gate,
                                gate_value=3, valid_mask=mask, retire=-1.0)

    text = _compiled_text(
        fn, spec((ROWS,), jnp.int32), spec((ROWS,), jnp.float32),
        spec((ROWS,), jnp.int32), spec((OUTS,), jnp.float32),
        spec((OUTS,), jnp.bool_),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("epilogue", [False, True])
def test_segment_max_compiles(spec, epilogue):
    def fn(vals, ids, gate, init, mask):
        if not epilogue:
            return segment_max_pallas(vals, ids, OUTS)
        return segment_max_pallas(vals, ids, OUTS, init=init, gate_ids=gate,
                                  gate_value=3, valid_mask=mask, retire=0.0)

    text = _compiled_text(
        fn, spec((ROWS,), jnp.float32), spec((ROWS,), jnp.int32),
        spec((ROWS,), jnp.int32), spec((OUTS,), jnp.float32),
        spec((OUTS,), jnp.bool_),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_cms_update_compiles(spec, dtype):
    text = _compiled_text(
        cms_update_pallas, spec((CMS_DEPTH, CMS_WIDTH), dtype),
        spec((CMS_DEPTH, CMS_BATCH), jnp.int32), spec((CMS_BATCH,), dtype),
    )
    assert "tpu_custom_call" in text


def test_hll_update_compiles(spec):
    text = _compiled_text(
        hll_update_pallas, spec((HLL_REGISTERS,), jnp.float32),
        spec((CMS_BATCH,), jnp.int32), spec((CMS_BATCH,), jnp.int32),
    )
    assert "tpu_custom_call" in text


# each kernel's custom call carries its ``name=``, which is the op's name
# in a device trace (``__unknown_`` without one)
@pytest.mark.parametrize("name", ["histogram", "segmax", "cms_update"])
def test_kernel_custom_call_is_named(spec, name):
    n = 1 << 16
    fn, args = {
        "histogram": (functools.partial(histogram_pallas, num_bins=256),
                      [spec((n,), jnp.int32)]),
        "segmax": (functools.partial(segment_max_pallas, num_segments=256),
                   [spec((n,), jnp.float32), spec((n,), jnp.int32)]),
        "cms_update": (cms_update_pallas,
                       [spec((CMS_DEPTH, CMS_WIDTH), jnp.int32),
                        spec((CMS_DEPTH, n), jnp.int32),
                        spec((n,), jnp.int32)]),
    }[name]
    calls = [line.split("=", 1)[0].split()[-1]
             for line in _compiled_text(fn, *args).splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert calls and all(c.startswith(f"%{name}.") for c in calls)
