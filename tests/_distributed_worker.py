"""8-device worker exercising repro.dist — run with forced host devices."""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.ref import ref_run_all_queries
from repro.core.table import Table
from repro.dist import (
    distributed_queries,
    distributed_queries_naive,
    distributed_unique_count,
)
from repro.dist.compress import psum_bf16, psum_int8

assert len(jax.devices()) == 8, jax.devices()


def check_queries_match_oracle():
    """CSR-shard path == pre-CSR flat-exchange path == NumPy oracle."""
    mesh = jax.make_mesh((8,), ("rows",))
    rng = np.random.default_rng(0)
    n = 8 * 2048
    src = rng.integers(0, 300, n).astype(np.int32)
    dst = rng.integers(0, 500, n).astype(np.int32)
    w = rng.integers(1, 5, n).astype(np.int32)

    def fn(src, dst, w):
        t = Table.from_dict({"src": src, "dst": dst, "n_packets": w})
        return distributed_queries(t, "rows")

    def fn_naive(src, dst, w):
        t = Table.from_dict({"src": src, "dst": dst, "n_packets": w})
        return distributed_queries_naive(t, "rows")

    f = jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=(P("rows"),) * 3, out_specs=P())
    )
    g = jax.jit(
        jax.shard_map(fn_naive, mesh=mesh, in_specs=(P("rows"),) * 3, out_specs=P())
    )
    res, res_naive = f(src, dst, w), g(src, dst, w)
    assert int(res["overflow"]) == 0
    for k, v in ref_run_all_queries(src, dst, w).items():
        assert int(res[k]) == v, (k, int(res[k]), v)
        assert int(res_naive[k]) == v, ("naive", k, int(res_naive[k]), v)


def check_skewed_keys_still_exact():
    """Zipf-skewed sources: heavy keys co-locate; exactness must hold."""
    mesh = jax.make_mesh((8,), ("rows",))
    rng = np.random.default_rng(1)
    n = 8 * 2048
    src = (rng.zipf(1.5, n) % 100).astype(np.int32)
    dst = (rng.zipf(1.3, n) % 200).astype(np.int32)

    def fn(src, dst):
        t = Table.from_dict({"src": src, "dst": dst})
        return distributed_queries(t, "rows", overflow_factor=4.0)

    f = jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=(P("rows"),) * 2, out_specs=P())
    )
    res = f(src, dst)
    ref = ref_run_all_queries(src, dst)
    if int(res["overflow"]) == 0:
        for k, v in ref.items():
            assert int(res[k]) == v, (k, int(res[k]), v)
    else:
        # overflow is *reported*, never silent — count-queries may undercount
        assert int(res["valid_packets"]) == ref["valid_packets"]


def check_multi_pod_axes():
    mesh = jax.make_mesh((2, 4), ("pod", "rows"))
    rng = np.random.default_rng(2)
    x = rng.integers(0, 1000, 8 * 1024).astype(np.int32)

    def fn(x):
        return distributed_unique_count(x, ("pod", "rows"))

    f = jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=(P(("pod", "rows")),), out_specs=(P(), P()))
    )
    cnt, ov = f(x)
    assert int(ov) == 0
    assert int(cnt) == len(np.unique(x))


def check_compression():
    mesh = jax.make_mesh((8,), ("dp",))
    rng = np.random.default_rng(3)
    g = rng.standard_normal((8, 512)).astype(np.float32) * 0.01

    def fn(x):
        exact = jax.lax.psum(x, "dp")
        b = psum_bf16(x, "dp")
        q, res = psum_int8(x, "dp")
        return exact, b, q, res

    f = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P("dp"),),
            out_specs=(P(None), P(None), P(None), P("dp")),  # residual is local
        )
    )
    exact, b, q, res = [np.asarray(v) for v in f(g)]
    exact, b, q = exact[0], b[0], q[0]
    assert np.allclose(b, exact, rtol=1e-2, atol=1e-3), "bf16 psum too far off"
    assert np.allclose(q, exact, rtol=0.15, atol=5e-3), "int8 psum too far off"
    # error feedback residual bounded by one quantization step
    step = np.abs(g).max() / 127.0
    assert np.abs(res).max() <= step + 1e-6


def check_distributed_anonymize():
    from repro.core.ref import ref_anonymize_check
    from repro.dist.anonymize import distributed_anonymize

    mesh = jax.make_mesh((8,), ("rows",))
    rng = np.random.default_rng(4)
    n = 8 * 2048
    src = rng.integers(0, 3000, n).astype(np.int32)
    dst = rng.integers(1000, 5000, n).astype(np.int32)
    f = jax.jit(jax.shard_map(
        lambda s, d, k: distributed_anonymize(
            Table.from_dict({"src": s, "dst": d}), k, "rows"),
        mesh=mesh, in_specs=(P("rows"), P("rows"), P()),
        out_specs={"src": P("rows"), "dst": P("rows"),
                   "n_ips": P(), "overflow": P()}))
    out = f(src, dst, jax.random.key(0))
    assert int(out["overflow"]) == 0
    assert int(out["n_ips"]) == len(np.unique(np.concatenate([src, dst])))
    assert ref_anonymize_check(
        src.astype(np.int64), dst.astype(np.int64),
        np.asarray(out["src"]), np.asarray(out["dst"]))


def check_stream_state_distributed_merge():
    """Streamed state merged through the repro.dist shard_map path.

    An engine accumulates micro-batches; snapshot(distributed=True) routes
    the accumulated link table through distributed_scalar_queries over the
    8 forced devices — the 'merge sharded stream state through repro.dist'
    contract.  Scalars must stay exact.
    """
    from repro.challenge.pipeline import window_column
    from repro.data.rmat import synthetic_packets
    from repro.stream import StreamConfig, StreamEngine

    n, nw = 1 << 12, 4
    cols = synthetic_packets(n, scale=12, seed=7)
    src = cols["src"].astype(np.int32)
    dst = cols["dst"].astype(np.int32)
    win = window_column(cols["ts"], nw)
    eng = StreamEngine(StreamConfig(
        batch_capacity=1024, link_capacity=n, n_windows=nw, ip_bins=64,
        top_k=5, backend="xla",
    ))
    for i in range(0, n, 1024):
        eng.ingest(src[i:i + 1024], dst[i:i + 1024], win[i:i + 1024])
    snap = eng.snapshot(distributed=True)
    assert snap.overflow == 0
    for k, v in ref_run_all_queries(src.astype(np.int64),
                                    dst.astype(np.int64)).items():
        assert int(getattr(snap.results.scalars, k)) == v, k


if __name__ == "__main__":
    check_queries_match_oracle()
    check_skewed_keys_still_exact()
    check_multi_pod_axes()
    check_compression()
    check_distributed_anonymize()
    check_stream_state_distributed_merge()
    print("ALL_DISTRIBUTED_OK")
