"""network-sensing — the PAPER'S OWN pipeline as a first-class arch config.

The Anonymized Network Sensing Graph Challenge end-to-end compute phase:
the 14 Table III queries + anonymization over a row-sharded packet table
(2^26 rows for the dry-run ≈ 1/16 of the challenge's 2^30, so the per-device
shard matches a full-scale 8192-device deployment row-for-row).

Cells lower a jit(jax.shard_map(...)) over the production mesh — this is the
paper's technique under the multi-pod dry-run, distinct from the 40
assigned-architecture cells.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.table import Table
from ..dist.relational import distributed_queries
from .common import ArchSpec, Cell, MeshAxes

ARCH_ID = "network-sensing"

SHAPES = {
    "queries_64m": dict(kind="serve", n_rows=1 << 26),
    "queries_16m": dict(kind="serve", n_rows=1 << 24),
}


def build_cell(shape: str, mp: MeshAxes) -> Optional[Cell]:
    info = SHAPES[shape]
    n = info["n_rows"]
    axis_names = mp.all_axes
    a_col = jax.ShapeDtypeStruct((n,), jnp.int32)
    col_spec = P(axis_names)

    if mp.mesh is None:
        return None  # shard_map cells need the concrete mesh

    def queries_fn(src, dst, w):
        t = Table.from_dict({"src": src, "dst": dst, "n_packets": w})
        return distributed_queries(t, axis_names)

    step = jax.shard_map(
        queries_fn, mesh=mp.mesh,
        in_specs=(col_spec, col_spec, col_spec),
        out_specs=P(),
    )
    return Cell(arch=ARCH_ID, shape=shape, kind="serve", step_fn=step,
                abstract_args=(a_col, a_col, a_col),
                arg_pspecs=(col_spec, col_spec, col_spec),
                note="paper pipeline: 14 challenge queries, hash-partition "
                     "all_to_all + local sort-groupby + psum/pmax merge")


def smoke():
    from ..core.queries import run_all_queries
    from ..core.ref import ref_run_all_queries

    rng = np.random.default_rng(0)
    src = rng.integers(0, 50, 512).astype(np.int32)
    dst = rng.integers(0, 50, 512).astype(np.int32)
    t = Table.from_dict({"src": jnp.asarray(src), "dst": jnp.asarray(dst)})
    res = jax.jit(run_all_queries)(t)
    ref = ref_run_all_queries(src, dst)
    for k, v in ref.items():
        assert int(getattr(res, k)) == v, k
    return {"unique_links": int(res.unique_links)}


SPEC = ArchSpec(arch=ARCH_ID, family="pipeline", shapes=tuple(SHAPES),
                build_cell=build_cell, smoke=smoke)
