"""Example: the paper's pipeline at pod scale (8 simulated devices).

Shards a packet table over 8 host devices, runs the hash-partition
all_to_all distributed queries (dist/relational.py), and checks exactness
vs the single-device path — the "2^30 edges won't fit one 16 GB chip"
scenario from DESIGN.md §5.

NOTE: re-execs itself with XLA_FLAGS to force 8 host devices.

    PYTHONPATH=src python examples/distributed_analytics.py
"""
import os
import sys

if "XLA_FLAGS" not in os.environ or "host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.ref import ref_run_all_queries
from repro.core.table import Table
from repro.dist import distributed_queries
from repro.data.rmat import synthetic_packets


def main(n: int = 1 << 20) -> None:
    print(f"devices: {len(jax.devices())}")
    cols = synthetic_packets(n, scale=20, seed=0)
    src = cols["src"].astype(np.int32)
    dst = cols["dst"].astype(np.int32)

    mesh = jax.make_mesh((8,), ("rows",))
    fn = jax.jit(jax.shard_map(
        lambda s, d: distributed_queries(
            Table.from_dict({"src": s, "dst": d}), "rows"),
        mesh=mesh, in_specs=(P("rows"), P("rows")), out_specs=P(),
    ))
    out = fn(src, dst)
    ref = ref_run_all_queries(src, dst)
    print(f"{'query':28s}{'8-shard':>12s}{'oracle':>12s}")
    for k, v in ref.items():
        got = int(out[k])
        print(f"{k:28s}{got:12,}{v:12,}")
        assert got == v, k
    assert int(out["overflow"]) == 0
    print(f"overflow=0; all {len(ref)} distributed queries exact ✓")


if __name__ == "__main__":
    main()
