"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import flash_attention, flash_attention_pallas
from repro.kernels.histogram import histogram_pallas
from repro.kernels.ops import cms_update
from repro.kernels.sketch import cms_update_pallas, hll_update_pallas
from repro.kernels.ref import (
    ref_attention,
    ref_cms_update,
    ref_histogram,
    ref_hll_update,
    ref_segment_matmul,
)
from repro.kernels.segment_matmul import segment_matmul_pallas

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------ histogram

@pytest.mark.parametrize("n", [1, 100, 1024, 5000])
@pytest.mark.parametrize("num_bins", [1, 7, 512, 1000])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_histogram_sweep(n, num_bins, dtype):
    ids = RNG.integers(-2, num_bins + 2, n).astype(np.int32)  # incl. out-of-range
    w = (RNG.integers(1, 10, n) if dtype == np.int32 else RNG.random(n)).astype(dtype)
    got = histogram_pallas(jnp.asarray(ids), num_bins, jnp.asarray(w), interpret=True)
    want = ref_histogram(jnp.asarray(ids), num_bins, jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_histogram_unweighted():
    ids = RNG.integers(0, 50, 777).astype(np.int32)
    got = histogram_pallas(jnp.asarray(ids), 50, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.bincount(ids, minlength=50))


@given(st.lists(st.integers(0, 31), min_size=1, max_size=300))
@settings(max_examples=20, deadline=None)
def test_histogram_property(ids):
    ids = np.array(ids, np.int32)
    got = histogram_pallas(jnp.asarray(ids), 32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.bincount(ids, minlength=32))


@pytest.mark.parametrize("block_rows,block_bins", [(256, 128), (1024, 512), (128, 1024)])
def test_histogram_block_shapes(block_rows, block_bins):
    ids = RNG.integers(0, 900, 3000).astype(np.int32)
    got = histogram_pallas(
        jnp.asarray(ids), 900, block_rows=block_rows, block_bins=block_bins, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.bincount(ids, minlength=900))


# -------------------------------------------------------------- segment matmul

@pytest.mark.parametrize("n,d,s", [(1, 1, 1), (100, 64, 10), (3000, 96, 500), (512, 200, 512)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_segment_matmul_sweep(n, d, s, dtype):
    x = RNG.standard_normal((n, d)).astype(dtype)
    seg = RNG.integers(0, s, n).astype(np.int32)
    got = segment_matmul_pallas(jnp.asarray(x), jnp.asarray(seg), s, interpret=True)
    want = ref_segment_matmul(jnp.asarray(x).astype(jnp.float32), jnp.asarray(seg), s)
    tol = 1e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_segment_matmul_out_of_range_dropped():
    x = np.ones((8, 4), np.float32)
    seg = np.array([0, 1, 2, 3, -1, 99, 0, 1], np.int32)
    got = segment_matmul_pallas(jnp.asarray(x), jnp.asarray(seg), 4, interpret=True)
    np.testing.assert_allclose(np.asarray(got).sum(), 6 * 4)


# ------------------------------------------------------------- flash attention

@pytest.mark.parametrize(
    "b,hq,hkv,lq,lkv,d",
    [
        (1, 1, 1, 128, 128, 64),     # MHA square
        (2, 8, 2, 256, 256, 64),     # GQA 4:1
        (1, 4, 4, 96, 96, 128),      # non-multiple of block
        (2, 8, 1, 1, 512, 64),       # decode: single query vs KV cache (MQA)
        (1, 2, 2, 64, 320, 32),      # chunked prefill: lq < lkv
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(b, hq, hkv, lq, lkv, d, causal):
    q = RNG.standard_normal((b, hq, lq, d)).astype(np.float32)
    k = RNG.standard_normal((b, hkv, lkv, d)).astype(np.float32)
    v = RNG.standard_normal((b, hkv, lkv, d)).astype(np.float32)
    got = flash_attention_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal, interpret=True
    )
    want = ref_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [1, 64, 200, 4096])
def test_flash_attention_sliding_window(window):
    q = RNG.standard_normal((1, 2, 256, 64)).astype(np.float32)
    k = RNG.standard_normal((1, 2, 256, 64)).astype(np.float32)
    v = RNG.standard_normal((1, 2, 256, 64)).astype(np.float32)
    got = flash_attention_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), window=window, interpret=True
    )
    want = ref_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    q = jnp.asarray(RNG.standard_normal((1, 4, 128, 64)), dtype)
    k = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), dtype)
    v = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), dtype)
    got = flash_attention_pallas(q, k, v, interpret=True).astype(jnp.float32)
    want = ref_attention(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_flash_attention_block_sizes():
    q = RNG.standard_normal((1, 2, 200, 64)).astype(np.float32)
    k = RNG.standard_normal((1, 2, 200, 64)).astype(np.float32)
    v = RNG.standard_normal((1, 2, 200, 64)).astype(np.float32)
    for bq, bk in [(64, 64), (128, 256), (32, 128)]:
        got = flash_attention_pallas(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            block_q=bq, block_k=bk, interpret=True,
        )
        want = ref_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_attention_grad_matches_ref():
    """custom_vjp backward == jnp attention VJP."""
    q = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.float32)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, None, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------- sketch kernels

@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("n", [1, 100, 1024, 5000])
# (4, 4096) is the sketch tier's default Count-Min: every block spans the
# whole depth axis, as the TPU's (8, 128) block tiling requires.
@pytest.mark.parametrize("depth,width", [(1, 64), (4, 512), (3, 1000), (4, 4096)])
def test_cms_update_sweep(n, depth, width, dtype):
    counts = RNG.integers(0, 50, (depth, width)).astype(dtype)
    # incl. out-of-range ids and -1 = masked proposal, per the contract
    ids = RNG.integers(-2, width + 2, (depth, n)).astype(np.int32)
    props = RNG.integers(1, 100, n).astype(dtype)
    got = cms_update_pallas(
        jnp.asarray(counts), jnp.asarray(ids), jnp.asarray(props),
        interpret=True,
    )
    want = ref_cms_update(jnp.asarray(counts), jnp.asarray(ids),
                          jnp.asarray(props))
    assert np.asarray(got).dtype == dtype  # counts dtype is preserved
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # cells never fall below their running value (init semantics)
    assert (np.asarray(got) >= counts).all()


def test_cms_update_int32_exact_past_float32_mantissa():
    """int32 counts must stay exact where float32 cells would round:
    2^24 + 1 is not representable in float32, and the sketch tier's
    never-underestimate guarantee depends on it surviving verbatim."""
    big = np.int32(1 << 24)
    counts = np.full((2, 64), big, np.int32)
    ids = np.zeros((2, 1), np.int32)
    props = np.array([big + 1], np.int32)
    for out in (
        cms_update_pallas(jnp.asarray(counts), jnp.asarray(ids),
                          jnp.asarray(props), interpret=True),
        ref_cms_update(jnp.asarray(counts), jnp.asarray(ids),
                       jnp.asarray(props)),
    ):
        assert int(np.asarray(out)[0, 0]) == int(big) + 1
        assert int(np.asarray(out)[1, 0]) == int(big) + 1


def test_cms_update_empty_proposals_is_identity():
    counts = RNG.integers(0, 9, (4, 128)).astype(np.float32)
    got = cms_update_pallas(
        jnp.asarray(counts),
        jnp.zeros((4, 0), jnp.int32),
        jnp.zeros((0,), jnp.float32),
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), counts)


def test_cms_update_all_masked_is_identity():
    counts = RNG.integers(0, 9, (2, 64)).astype(np.float32)
    ids = np.full((2, 33), -1, np.int32)
    props = RNG.integers(1, 9, 33).astype(np.float32)
    got = cms_update_pallas(jnp.asarray(counts), jnp.asarray(ids),
                            jnp.asarray(props), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), counts)


@pytest.mark.parametrize("block_props,block_width", [(256, 128), (1024, 512), (128, 1024)])
def test_cms_update_block_shapes(block_props, block_width):
    counts = RNG.integers(0, 20, (4, 900)).astype(np.float32)
    ids = RNG.integers(0, 900, (4, 3000)).astype(np.int32)
    props = RNG.integers(1, 50, 3000).astype(np.float32)
    got = cms_update_pallas(
        jnp.asarray(counts), jnp.asarray(ids), jnp.asarray(props),
        block_props=block_props, block_width=block_width, interpret=True,
    )
    want = ref_cms_update(jnp.asarray(counts), jnp.asarray(ids),
                          jnp.asarray(props))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.lists(st.tuples(st.integers(-1, 63), st.integers(1, 40)),
                min_size=1, max_size=200))
@settings(max_examples=15, deadline=None)
def test_cms_update_property(pairs):
    ids = np.array([p[0] for p in pairs], np.int32)[None, :]
    props = np.array([p[1] for p in pairs], np.float32)
    counts = np.zeros((1, 64), np.float32)
    got = np.asarray(cms_update_pallas(
        jnp.asarray(counts), jnp.asarray(ids), jnp.asarray(props),
        interpret=True))
    want = np.zeros(64)
    for c, p in pairs:
        if c >= 0:
            want[c] = max(want[c], p)
    np.testing.assert_array_equal(got[0], want)


@pytest.mark.parametrize("n", [1, 500, 4096])
@pytest.mark.parametrize("m", [16, 1024])
def test_hll_update_sweep(n, m):
    regs = RNG.integers(0, 20, m).astype(np.float32)
    ids = RNG.integers(-2, m + 2, n).astype(np.int32)
    rhos = RNG.integers(1, 33, n).astype(np.float32)
    got = hll_update_pallas(jnp.asarray(regs), jnp.asarray(ids),
                            jnp.asarray(rhos), interpret=True)
    want = ref_hll_update(jnp.asarray(regs), jnp.asarray(ids),
                          jnp.asarray(rhos))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got) >= regs).all()  # registers only ever grow


def test_cms_update_dispatch_backends_agree():
    counts = RNG.integers(0, 10, (4, 256)).astype(np.float32)
    ids = RNG.integers(-1, 256, (4, 777)).astype(np.int32)
    props = RNG.integers(1, 30, 777).astype(np.float32)
    outs = [
        np.asarray(cms_update(jnp.asarray(counts), jnp.asarray(ids),
                              jnp.asarray(props), backend=b))
        for b in ("xla", "interpret")
    ]
    np.testing.assert_array_equal(outs[0], outs[1])
