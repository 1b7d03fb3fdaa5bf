"""Beyond-paper: the distributed (shard_map) query path — the paper's
single-GPU pipeline scaled out over every device of this process.

Reports the single-device ``run_all_queries`` wall beside the
``distributed_queries`` suite sharded over all local devices, with both
checked against the NumPy oracle.  Everything runs in the harness's own
process: a child process could not reach an accelerator the parent holds.
With one device the lane says so and emits no number; on a CPU host,
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives it eight.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.queries import run_all_queries
from repro.core.ref import ref_run_all_queries
from repro.core.table import Table
from repro.dist import distributed_queries

from .common import emit, time_fn


def run(n: int = 1 << 21) -> None:
    n_dev = len(jax.devices())
    kind = jax.devices()[0].device_kind
    if n_dev < 2:
        print(f"distributed: needs >= 2 devices, found {n_dev} ({kind}); "
              "no rows emitted", file=sys.stderr)
        return
    n -= n % n_dev  # equal row shards
    rng = np.random.default_rng(0)
    src = rng.integers(0, 1 << 18, n).astype(np.int32)
    dst = rng.integers(0, 1 << 18, n).astype(np.int32)
    ref = ref_run_all_queries(src, dst)

    t = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst)})
    f1 = jax.jit(run_all_queries)
    single = f1(t)
    t_single = time_fn(f1, t)

    mesh = jax.make_mesh((n_dev,), ("rows",))
    fn = jax.jit(jax.shard_map(
        lambda s, d: distributed_queries(
            Table.from_dict({"src": s, "dst": d}), "rows"),
        mesh=mesh, in_specs=(P("rows"), P("rows")), out_specs=P()))
    out = fn(src, dst)
    t_dist = time_fn(fn, src, dst)

    ok_single = all(int(getattr(single, k)) == v for k, v in ref.items())
    ok = all(int(out[k]) == v for k, v in ref.items()) \
        and int(out["overflow"]) == 0
    if not (ok and ok_single):
        raise AssertionError(
            f"distributed lane disagrees with the NumPy oracle "
            f"(single={ok_single}, {n_dev} shards={ok})")
    emit("distributed/all14_single_device", t_single,
         f"n={n} device={kind} exact=True")
    emit(f"distributed/all14_{n_dev}shards", t_dist,
         f"n={n} device={kind} exact=True")


if __name__ == "__main__":
    run()
