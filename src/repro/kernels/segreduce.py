"""Pallas TPU segmented-reduction kernel — the GraphBLAS-lite ``mxv`` path.

``core/sparse.py`` expresses masked ``mxv``/``vxm`` as "combine one value
per stored entry, then reduce entries into their row (or column) segment".
The sum monoid is exactly the histogram kernel's one-hot matmul
(``histogram_pallas`` with the products as weights); what that kernel cannot
do is the **max monoid** — MXU matmuls only accumulate by addition.  This
module adds the max variant in the same sequential-grid shape
(DESIGN.md §2.1): for a block of ``Bn`` entries and a tile of ``St``
segments,

    partial[1, St] = max over entries of where(onehot(seg_ids), vals, -inf)

runs on the VPU (compare + select + axis-0 max), and consecutive row blocks
revisit the same output tile resident in VMEM, folding partials with
``jnp.maximum`` — the TPU replacement for CUDA ``atomicMax``.

Grid: ``(num_seg_tiles, num_row_blocks)``; VMEM per step is
``2·Bn + St + Bn·St`` fp32 elements — the histogram kernel's budget plus
one value row.  Empty segments report ``-inf`` (the max monoid identity)
unless an ``init`` accumulator seeds the tile.  Block shapes default to
:mod:`repro.kernels.defaults`, overridden per shape bucket by the
autotuner; the ``valid_mask`` fusion epilogue mirrors the histogram
kernel's (DESIGN.md §2.9).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .defaults import DEFAULT_BLOCK_ROWS, DEFAULT_BLOCK_SEGS

__all__ = ["segment_max_pallas"]

_NEG_INF = float("-inf")


def _make_segmax_kernel(*, block_segs: int, accum: bool, masked: bool,
                        retire: float):
    """Kernel-body factory; operand layout mirrors the histogram kernel's
    (init tile, then mask tile)."""

    def kernel(*refs):
        refs = list(refs)
        out_ref = refs.pop()
        ids_ref, v_ref = refs[0], refs[1]
        nxt = 2
        if accum:
            init_ref = refs[nxt]
            nxt += 1
        if masked:
            mask_ref = refs[nxt]

        j = pl.program_id(1)  # entry-block index (inner, accumulating)
        i = pl.program_id(0)  # segment-tile index (outer)
        ids = ids_ref[...]  # (1, Bn) int32
        v = v_ref[...].astype(jnp.float32)  # (1, Bn)
        base = i * block_segs
        segs = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_segs), 1)
        sel = ids.T == segs  # (Bn, St)
        cand = jnp.where(sel, jnp.broadcast_to(v.T, sel.shape), _NEG_INF)
        partial = jnp.max(cand, axis=0, keepdims=True)  # (1, St)

        @pl.when(j == 0)
        def _init():
            # accumulate variant seeds from init — ``out = maximum(init,
            # segment_max(...))`` in one dispatch
            out_ref[...] = (init_ref[...].astype(jnp.float32) if accum
                            else jnp.full_like(out_ref, _NEG_INF))

        out_ref[...] = jnp.maximum(out_ref[...], partial)

        if masked:
            @pl.when(j == pl.num_programs(1) - 1)
            def _retire():
                out_ref[...] = jnp.where(
                    mask_ref[...] != 0, out_ref[...], jnp.float32(retire)
                )

    return kernel


def segment_max_pallas(
    vals: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    *,
    init: Optional[jnp.ndarray] = None,
    valid_mask: Optional[jnp.ndarray] = None,
    retire: float = _NEG_INF,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_segs: int = DEFAULT_BLOCK_SEGS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-segment max of ``vals`` over int32 ``seg_ids``.

    Out-of-range ids (including the jaxdf padding id) are dropped; inputs
    are padded to block multiples with id == -1 (matches no segment).
    Empty segments yield ``-inf`` (max monoid identity) unless ``init``
    (shape ``(num_segments,)``) seeds the output.

    Fused epilogue (same contract as :func:`histogram_pallas`):
    ``valid_mask`` + static ``retire`` overwrite masked-out segments after
    the reduction.
    Returns float32 of shape (num_segments,).
    """
    n = vals.shape[0]
    if n == 0:
        # zero row blocks would skip the kernel body (and its output-tile
        # init) entirely, returning uninitialized memory — emit the monoid
        # identity / accumulator directly
        out = (jnp.full((num_segments,), _NEG_INF, jnp.float32)
               if init is None else init.astype(jnp.float32))
        if valid_mask is not None:
            out = jnp.where(valid_mask, out, jnp.float32(retire))
        return out
    masked = valid_mask is not None
    n_pad = -n % block_rows
    s_pad = -num_segments % block_segs
    ids_p = jnp.pad(seg_ids.astype(jnp.int32), (0, n_pad), constant_values=-1)[None, :]
    v_p = jnp.pad(vals.astype(jnp.float32), (0, n_pad))[None, :]
    segs_padded = num_segments + s_pad

    grid = (segs_padded // block_segs, ids_p.shape[1] // block_rows)
    row_spec = pl.BlockSpec((1, block_rows), lambda i, j: (0, j))
    seg_spec = pl.BlockSpec((1, block_segs), lambda i, j: (0, i))
    in_specs = [row_spec, row_spec]
    operands = [ids_p, v_p]
    if init is not None:
        init_p = jnp.pad(
            init.astype(jnp.float32), (0, s_pad), constant_values=_NEG_INF
        )[None, :]
        in_specs.append(seg_spec)
        operands.append(init_p)
    if masked:
        mask_p = jnp.pad(valid_mask.astype(jnp.int32), (0, s_pad))[None, :]
        in_specs.append(seg_spec)
        operands.append(mask_p)
    kernel = _make_segmax_kernel(
        block_segs=block_segs, accum=init is not None, masked=masked,
        retire=float(retire),
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=seg_spec,
        out_shape=jax.ShapeDtypeStruct((1, segs_padded), jnp.float32),
        interpret=interpret,
        name="segmax",
    )(*operands)
    return out[0, :num_segments]
