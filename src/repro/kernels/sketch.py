"""Pallas TPU sketch-update kernels — the approximate tier's hot path.

The bounded-memory analytics tier (DESIGN.md §2.6) folds packet batches
into three mergeable summaries: a Count–Min sketch (conservative-update
variant), HyperLogLog registers, and a space-saving heavy-hitter table.
The first two have the same inner loop: **scatter-max into a small dense
grid** — exactly the shape of :mod:`repro.kernels.segreduce`, so both ride
the sequential-grid formulation (DESIGN.md §2.1): for a block of ``Bn``
update proposals and a tile of ``Wt`` cells,

    partial[1, Wt] = max over proposals of where(onehot(col_ids), prop, -inf)

runs on the VPU, and consecutive proposal blocks revisit the same output
tile resident in VMEM, folding partials with ``jnp.maximum`` — the TPU
replacement for CUDA ``atomicMax`` (what cuDF-style CMS kernels use).

``cms_update_pallas`` is the depth-row generalisation: the grid is
``(num_width_tiles, num_prop_blocks)``, each block holds every depth row,
and every row scatters the *same* proposal vector through its own hash
row of ``col_ids``.  The conservative-update rule (propose
``min_r counts[r, h_r(x)] + n_x``, take the cell-wise max) means the cell
update is a pure max fold, so the existing accumulate idiom (seed the
output tile from the running counts) gives batch-into-state folding in one
dispatch.  ``hll_update_pallas`` is the 1-row case and simply re-exports
the segmented-max kernel: an HLL register fold *is* a segmented max.

VMEM per step is ``(depth + 1)·Bn + 2·depth·Wt + Bn·Wt`` 32-bit
elements — the segreduce budget with the depth rows of ids and counts.
NumPy oracles: :func:`repro.kernels.ref.ref_cms_update` /
:func:`repro.kernels.ref.ref_hll_update` (interpret-parity tested in
tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .defaults import DEFAULT_BLOCK_PROPS, DEFAULT_BLOCK_WIDTH
from .segreduce import segment_max_pallas

__all__ = ["cms_update_pallas", "hll_update_pallas"]

_NEG_INF = float("-inf")


def _cms_kernel(ids_ref, prop_ref, init_ref, out_ref, *, depth: int,
                block_width: int, sentinel):
    k = pl.program_id(1)  # proposal-block index (inner, accumulating)
    i = pl.program_id(0)  # width-tile index
    prop = prop_ref[...]  # (1, Bn) — shared across rows
    cols = i * block_width + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_width), 1
    )
    fill = prop.dtype.type(sentinel)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = init_ref[...]

    for d in range(depth):  # static unroll: one (Bn, Wt) select per row
        ids = ids_ref[d:d + 1, :]  # (1, Bn) int32 — row d's hashed columns
        sel = ids.T == cols  # (Bn, Wt)
        cand = jnp.where(sel, jnp.broadcast_to(prop.T, sel.shape), fill)
        partial = jnp.max(cand, axis=0, keepdims=True)  # (1, Wt)
        out_ref[d:d + 1, :] = jnp.maximum(out_ref[d:d + 1, :], partial)


def cms_update_pallas(
    counts: jnp.ndarray,
    col_ids: jnp.ndarray,
    proposals: jnp.ndarray,
    *,
    block_props: int = DEFAULT_BLOCK_PROPS,
    block_width: int = DEFAULT_BLOCK_WIDTH,
    interpret: bool = False,
) -> jnp.ndarray:
    """Conservative-update CMS fold: cell-wise max of the running ``counts``
    and the scatter-max of ``proposals`` through every hash row.

    Args:
      counts: ``(depth, width)`` running sketch counts — float32 or int32
        (the sketch tier stores int32 so counts stay exact past 2^24;
        proposals are cast to the same dtype).
      col_ids: ``(depth, n)`` int32 hashed column per (row, proposal);
        out-of-range ids (including -1 = masked proposal) are dropped.
      proposals: ``(n,)`` proposed new cell values (``est + batch_count``
        under the conservative-update rule) — shared by all depth rows.

    Returns ``(depth, width)`` in ``counts.dtype``; cells no proposal maps
    to keep their running value (``init`` semantics, not the monoid
    identity).
    """
    depth, width = counts.shape
    dtype = counts.dtype
    sentinel = (jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
                else _NEG_INF)
    n = col_ids.shape[1]
    if n == 0:
        # zero proposal blocks would skip the kernel body (and its output
        # tile init) entirely — the fold of nothing is the running counts
        return counts
    n_pad = -n % block_props
    w_pad = -width % block_width
    ids_p = jnp.pad(
        col_ids.astype(jnp.int32), ((0, 0), (0, n_pad)), constant_values=-1
    )
    prop_p = jnp.pad(proposals.astype(dtype), (0, n_pad))[None, :]
    init_p = jnp.pad(counts, ((0, 0), (0, w_pad)))
    width_padded = width + w_pad

    # Each block spans the whole depth axis: the TPU tiling wants the
    # last two block dims divisible by (8, 128) or equal to the array's,
    # and a depth-1 block of a (depth, n) array is neither.
    grid = (width_padded // block_width, ids_p.shape[1] // block_props)
    out = pl.pallas_call(
        functools.partial(
            _cms_kernel, depth=depth, block_width=block_width,
            sentinel=sentinel,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((depth, block_props), lambda i, k: (0, k)),
            pl.BlockSpec((1, block_props), lambda i, k: (0, k)),
            pl.BlockSpec((depth, block_width), lambda i, k: (0, i)),
        ],
        out_specs=pl.BlockSpec((depth, block_width), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((depth, width_padded), dtype),
        interpret=interpret,
        name="cms_update",
    )(ids_p, prop_p, init_p)
    return out[:, :width]


def hll_update_pallas(
    registers: jnp.ndarray,
    reg_ids: jnp.ndarray,
    rhos: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """HyperLogLog register fold — ``reg[j] = max(reg[j], max rho over j)``.

    An HLL fold *is* a segmented max with the running registers as the
    accumulator, so this is the 1-row case of the CMS kernel and dispatches
    straight to :func:`repro.kernels.segreduce.segment_max_pallas` with
    ``init=registers`` (out-of-range ids dropped, same contract).
    """
    return segment_max_pallas(
        rhos.astype(jnp.float32),
        reg_ids,
        registers.shape[0],
        init=registers,
        interpret=interpret,
    )
