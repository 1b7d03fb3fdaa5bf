"""jit'd public wrappers around the Pallas kernels, with regime dispatch.

Callers (core queries, GNN aggregation, attention layers) use these entry
points; each dispatches between the Pallas kernel (TPU, or interpret=True for
CPU validation) and the XLA fallback (= the oracle) based on problem regime
and the ``backend`` argument:

  * ``"xla"``       — pure-jnp path (paper-faithful "commodity ops only");
                      also what the multi-pod dry-run lowers (CPU container).
  * ``"pallas"``    — Pallas TPU kernel.
  * ``"interpret"`` — Pallas kernel body interpreted on CPU (tests).
  * ``"auto"``      — size heuristic: matmul-formulation kernels win when the
                      segment/bin count is small enough that onehot FLOPs
                      (2·n·S·d) stay under the scatter path's memory time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import autotune, ref
from .flash_attention import flash_attention, flash_attention_pallas
from .histogram import histogram_pallas
from .segment_matmul import segment_matmul_pallas
from .segreduce import segment_max_pallas
from .sketch import cms_update_pallas

__all__ = [
    "histogram",
    "windowed_histogram",
    "segmented_reduce",
    "segment_reduce",
    "cms_update",
    "attention",
]

# One-hot matmul beats scatter only while S is modest; see DESIGN.md §2 and
# the §2.2 napkin math (2·n·S flops vs ~12·n bytes of scatter traffic).
_MATMUL_SEGMENT_LIMIT = 4096


def _resolve(backend: str, num_out: int) -> str:
    """Map ``"auto"`` to a concrete backend by the §2.2 size heuristic."""
    if backend != "auto":
        return backend
    return "pallas" if (
        jax.default_backend() == "tpu" and num_out <= _MATMUL_SEGMENT_LIMIT
    ) else "xla"


def histogram(
    ids: jnp.ndarray,
    num_bins: int,
    weights: Optional[jnp.ndarray] = None,
    *,
    init: Optional[jnp.ndarray] = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """Weighted histogram with an optional accumulate path.

    ``init`` (float32, shape ``(num_bins,)``) is a running accumulator the
    batch folds into — ``out = init + histogram(ids, weights)`` — the
    mergeable-state primitive of the streaming engine (DESIGN.md §6).  On
    the Pallas path the accumulator seeds the output tile in VMEM instead
    of zeros, so accumulation costs no extra dispatch.
    """
    backend = _resolve(backend, num_bins)
    if backend == "xla":
        out = ref.ref_histogram(ids, num_bins, weights)
        if init is not None:
            out = init.astype(jnp.float32) + out
        return out
    cfg = autotune.best_config("histogram", ids.shape[0], num_bins, "float32")
    return histogram_pallas(
        ids, num_bins, weights, init=init,
        interpret=(backend == "interpret"), **cfg,
    )


def windowed_histogram(
    win: jnp.ndarray,
    ids: jnp.ndarray,
    n_windows: int,
    num_bins: int,
    weights: Optional[jnp.ndarray] = None,
    *,
    init: Optional[jnp.ndarray] = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """Per-temporal-window histograms in ONE kernel dispatch.

    The challenge's multi-temporal analysis needs a histogram *per window*;
    dispatching the kernel once per window serializes n_windows tiny grids.
    Instead the (window, id) pair is fused into a single flattened bin space
    ``win * num_bins + id`` so every window batches through one
    ``histogram_pallas`` grid (the bin-tile axis simply grows n_windows-fold
    — same VMEM budget per step, DESIGN.md §2/§7).

    ``init`` (shape ``(n_windows, num_bins)``) is a running accumulator the
    batch folds into — the streaming engine's per-window activity merge
    (DESIGN.md §6).  Rows with ``win`` or ``ids`` outside range are dropped
    (fused id -1).  Returns float32 counts of shape (n_windows, num_bins).
    """
    ok = (win >= 0) & (win < n_windows) & (ids >= 0) & (ids < num_bins)
    fused = jnp.where(
        ok, win.astype(jnp.int32) * num_bins + ids.astype(jnp.int32), -1
    )
    flat_init = None if init is None else init.reshape(n_windows * num_bins)
    flat = histogram(
        fused, n_windows * num_bins, weights, init=flat_init, backend=backend
    )
    return flat.reshape(n_windows, num_bins)


def segmented_reduce(
    vals: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    *,
    op: str = "sum",
    init: Optional[jnp.ndarray] = None,
    valid_mask: Optional[jnp.ndarray] = None,
    retire=None,
    backend: str = "auto",
) -> jnp.ndarray:
    """1-D segmented reduction under a plus or max monoid — the reduction
    behind the GraphBLAS-lite ``mxv``/``vxm`` of :mod:`repro.core.sparse`.

    ``op="sum"`` is the histogram kernel with the values as weights (one-hot
    matmul on the MXU); ``op="max"`` dispatches the VPU compare-select
    kernel of :mod:`repro.kernels.segreduce` — MXU accumulation is additive,
    so the max monoid needs its own kernel.  Empty segments yield the monoid
    identity (0 / ``-inf``); ``init`` folds a running accumulator in the
    same dispatch.  Returns float32 of shape ``(num_segments,)``.

    Fused epilogue (DESIGN.md §2.9): ``valid_mask`` + static ``retire``
    (default: the monoid identity) overwrite masked-out segments last (the
    masked ``mxv``/``vxm`` of :mod:`repro.core.sparse`).
    """
    if op == "sum":
        backend = _resolve(backend, num_segments)
        r = 0.0 if retire is None else retire
        if backend == "xla":
            return ref.ref_segmented_reduce(
                vals, seg_ids, num_segments, op, init,
                valid_mask=valid_mask, retire=r,
            )
        cfg = autotune.best_config(
            "histogram", seg_ids.shape[0], num_segments, "float32"
        )
        return histogram_pallas(
            seg_ids, num_segments, vals, init=init, valid_mask=valid_mask,
            retire=float(r), interpret=(backend == "interpret"), **cfg,
        )
    if op != "max":
        raise ValueError(f"unknown segmented-reduce op {op!r}")
    backend = _resolve(backend, num_segments)
    r = float("-inf") if retire is None else retire
    if backend == "xla":
        return ref.ref_segmented_reduce(
            vals, seg_ids, num_segments, op, init,
            valid_mask=valid_mask, retire=r,
        )
    cfg = autotune.best_config(
        "segreduce", seg_ids.shape[0], num_segments, "float32"
    )
    return segment_max_pallas(
        vals, seg_ids, num_segments, init=init, valid_mask=valid_mask,
        retire=float(r), interpret=(backend == "interpret"), **cfg,
    )


def cms_update(
    counts: jnp.ndarray,
    col_ids: jnp.ndarray,
    proposals: jnp.ndarray,
    *,
    backend: str = "auto",
) -> jnp.ndarray:
    """Conservative-update Count–Min fold — the approximate tier's scatter
    (:mod:`repro.core.sketch`, DESIGN.md §2.6).

    Cell-wise max of the running ``(depth, width)`` counts and the
    scatter-max of ``proposals`` through each depth row's hashed
    ``col_ids`` — one dispatch folds a whole batch into the sketch, the
    same accumulate idiom as the histogram/segreduce ``init=`` paths.
    """
    backend = _resolve(backend, counts.shape[1])
    if backend == "xla":
        return ref.ref_cms_update(counts, col_ids, proposals)
    cfg = autotune.best_config(
        "cms", col_ids.shape[1], counts.shape[1], str(counts.dtype)
    )
    return cms_update_pallas(
        counts, col_ids, proposals, interpret=(backend == "interpret"), **cfg
    )


def segment_reduce(
    x: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    *,
    backend: str = "auto",
) -> jnp.ndarray:
    if backend == "auto":
        backend = "pallas" if (
            jax.default_backend() == "tpu" and num_segments <= _MATMUL_SEGMENT_LIMIT
        ) else "xla"
    if backend == "xla":
        return ref.ref_segment_matmul(x, seg_ids, num_segments)
    return segment_matmul_pallas(
        x, seg_ids, num_segments, interpret=(backend == "interpret")
    )


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    backend: str = "auto",
) -> jnp.ndarray:
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return ref.ref_attention(q, k, v, causal=causal, window=window, scale=scale)
    return flash_attention(
        q, k, v, causal, window, scale, backend == "interpret"
    )
