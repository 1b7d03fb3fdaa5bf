"""Pallas TPU histogram kernel — the ``value_counts`` hot path.

The paper's hottest ETL primitives (``value_counts``, packets-per-source,
fan-out counting) all reduce to a weighted histogram over *factorized* ids.
cuDF implements this with a global-atomic hash table; TPU has no global
atomics, so the TPU-native formulation is a **one-hot matmul**: for a block
of ``Bn`` rows and a tile of ``St`` bins,

    partial[1, St] = weights[1, Bn] @ onehot(ids)[Bn, St]

which runs on the MXU instead of scatter units.  Bin tiles are the outer grid
dimension; row blocks are the inner dimension and *revisit* the same output
tile, accumulating in VMEM (Pallas keeps an output block resident while
consecutive grid steps map to it — the sequential-grid TPU replacement for
CUDA atomics, per DESIGN.md §2).

Grid: ``(num_bin_tiles, num_row_blocks)``; VMEM working set per step is
``Bn + St + Bn·St`` elements — (1024, 512) tiles ≈ 2.3 MB fp32, well under
the ~16 MB v5e VMEM budget.  Block shapes default to
:mod:`repro.kernels.defaults` and are overridden per shape bucket by the
autotuner (:mod:`repro.kernels.autotune`).

Fusion epilogues (DESIGN.md §2.9): the kernel optionally fuses the two
scatter/gather chains that used to bracket it as separate XLA ops —

  * ``gate_ids``/``gate_value`` — a row contributes only when
    ``gate_ids[i] == gate_value`` (the windowed suite's per-window
    ``where(in_w, ...)`` slice select, folded into the one-hot compare);
  * ``valid_mask``/``retire`` — after the last row block accumulates, bins
    outside the mask are overwritten with the static ``retire`` value (the
    top-k pre-mask / mxv post-mask, folded into the final grid step).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .defaults import DEFAULT_BLOCK_BINS, DEFAULT_BLOCK_ROWS

__all__ = ["histogram_pallas", "DEFAULT_BLOCK_ROWS", "DEFAULT_BLOCK_BINS"]


def _make_hist_kernel(*, block_bins: int, gated: bool, accum: bool,
                      masked: bool, retire: float):
    """Build the histogram kernel body for one operand layout.

    Operand order (after ids/weights): gate row + gate scalar when
    ``gated``, init tile when ``accum``, mask tile when ``masked`` —
    mirrored exactly by the in_specs assembly in :func:`histogram_pallas`.
    """

    def kernel(*refs):
        refs = list(refs)
        out_ref = refs.pop()
        ids_ref, w_ref = refs[0], refs[1]
        nxt = 2
        if gated:
            gate_ref, gv_ref = refs[nxt], refs[nxt + 1]
            nxt += 2
        if accum:
            init_ref = refs[nxt]
            nxt += 1
        if masked:
            mask_ref = refs[nxt]

        j = pl.program_id(1)  # row-block index (inner, accumulating)
        i = pl.program_id(0)  # bin-tile index (outer)
        ids = ids_ref[...]  # (1, Bn) int32
        w = w_ref[...].astype(jnp.float32)  # (1, Bn)
        base = i * block_bins
        bins = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_bins), 1)
        keep = ids.T == bins  # (Bn, St)
        if gated:
            # per-row gate fused into the one-hot compare: a gated-out row
            # matches no bin, exactly the where(in_w, ...) pre-select
            keep = keep & (gate_ref[...].T == gv_ref[0, 0])
        onehot = keep.astype(jnp.float32)
        partial = jax.lax.dot_general(
            w, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (1, St)

        @pl.when(j == 0)
        def _init():
            # accumulate variant seeds from init (the streaming merge path —
            # kernels/ops.histogram ``init=``) instead of zeros
            out_ref[...] = (init_ref[...].astype(jnp.float32) if accum
                            else jnp.zeros_like(out_ref))

        out_ref[...] += partial

        if masked:
            @pl.when(j == pl.num_programs(1) - 1)
            def _retire():
                # post-reduce epilogue on the final revisit: masked-out bins
                # take the static retire value (top-k pre-mask / mxv mask)
                out_ref[...] = jnp.where(
                    mask_ref[...] != 0, out_ref[...], jnp.float32(retire)
                )

    return kernel


def histogram_pallas(
    ids: jnp.ndarray,
    num_bins: int,
    weights: Optional[jnp.ndarray] = None,
    *,
    init: Optional[jnp.ndarray] = None,
    gate_ids: Optional[jnp.ndarray] = None,
    gate_value=None,
    valid_mask: Optional[jnp.ndarray] = None,
    retire: float = 0.0,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_bins: int = DEFAULT_BLOCK_BINS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Weighted histogram over int32 ids; out-of-range ids are dropped.

    Inputs are padded to block multiples; padded rows get id == -1 (matches
    no bin).  ``init`` (shape ``(num_bins,)``) seeds the output instead of
    zeros — the mergeable-accumulator path: ``out = init + histogram(ids)``
    in one dispatch.

    Fused epilogues: ``gate_ids`` (shape of ``ids``) + ``gate_value``
    (scalar, may be traced) keep only rows with ``gate_ids[i] ==
    gate_value``; ``valid_mask`` (bool, shape ``(num_bins,)``) overwrites
    masked-out bins with ``retire`` *after* the reduction (and after the
    ``init`` fold).  ``retire`` must be a static Python number — it is
    baked into the kernel.  Returns float32 counts of shape (num_bins,).
    """
    n = ids.shape[0]
    if n == 0:
        # zero row blocks would skip the kernel body (and its output-tile
        # init), returning uninitialized memory — emit the identity directly
        out = (jnp.zeros((num_bins,), jnp.float32) if init is None
               else init.astype(jnp.float32))
        if valid_mask is not None:
            out = jnp.where(valid_mask, out, jnp.float32(retire))
        return out
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    gated = gate_ids is not None
    masked = valid_mask is not None
    n_pad = -n % block_rows
    b_pad = -num_bins % block_bins
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, n_pad), constant_values=-1)[None, :]
    w_p = jnp.pad(weights.astype(jnp.float32), (0, n_pad))[None, :]
    bins_padded = num_bins + b_pad

    grid = (bins_padded // block_bins, ids_p.shape[1] // block_rows)
    row_spec = pl.BlockSpec((1, block_rows), lambda i, j: (0, j))
    bin_spec = pl.BlockSpec((1, block_bins), lambda i, j: (0, i))
    in_specs = [row_spec, row_spec]
    operands = [ids_p, w_p]
    if gated:
        # padded gate rows are irrelevant (their id == -1 matches no bin);
        # the gate scalar rides as a (1, 1) operand so it may be traced
        gate_p = jnp.pad(gate_ids.astype(jnp.int32), (0, n_pad))[None, :]
        gv = jnp.asarray(gate_value, jnp.int32).reshape(1, 1)
        in_specs += [row_spec, pl.BlockSpec((1, 1), lambda i, j: (0, 0))]
        operands += [gate_p, gv]
    if init is not None:
        init_p = jnp.pad(init.astype(jnp.float32), (0, b_pad))[None, :]
        in_specs.append(bin_spec)
        operands.append(init_p)
    if masked:
        # int32 (not bool) VMEM tile; padded bins are masked out -> retire,
        # then sliced away below
        mask_p = jnp.pad(valid_mask.astype(jnp.int32), (0, b_pad))[None, :]
        in_specs.append(bin_spec)
        operands.append(mask_p)
    kernel = _make_hist_kernel(
        block_bins=block_bins, gated=gated, accum=init is not None,
        masked=masked, retire=float(retire),
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=bin_spec,
        out_shape=jax.ShapeDtypeStruct((1, bins_padded), jnp.float32),
        interpret=interpret,
        name="histogram",
    )(*operands)
    return out[0, :num_bins]
