"""Compiled-HLO peak-buffer budget of ``challenge.analyze`` (DESIGN.md §2.4).

The memory analog of the sort-budget smoke in tests/test_plan.py: the CSR
windowed path must keep ``analyze``'s peak live bytes (estimated from the
post-optimization HLO by ``launch/hloanalysis.peak_buffer_bytes``) pinned
and *independent of the window axis*.  Gated at the challenge's scale-17
capacity — compile-only, nothing executes.
"""
import jax
import pytest

from repro.challenge.pipeline import analyze_peak_buffer_bytes

jax.config.update("jax_platform_name", "cpu")

SCALE = 17
CAP = 1 << SCALE
GATE_WINDOWS = 32
# pinned absolute budget for the CSR path at scale 17 (measured ~18.6 MB;
# headroom for XLA layout drift).  Dense (n_windows, capacity) grids measured
# ~131 MB at 32 windows — regressions that re-densify the windowed state
# trip this.
CSR_PEAK_BUDGET_BYTES = 32e6


@pytest.fixture(scope="module")
def peaks():
    return {nw: analyze_peak_buffer_bytes(CAP, n_windows=nw)
            for nw in (8, GATE_WINDOWS)}


def test_csr_peak_budget_pinned(peaks):
    """THE memory acceptance gate: CSR analyze stays under the pinned
    scale-17 peak-buffer budget."""
    got = peaks[GATE_WINDOWS]
    assert got <= CSR_PEAK_BUDGET_BYTES, (
        f"CSR analyze peak {got / 1e6:.1f} MB exceeds the pinned "
        f"{CSR_PEAK_BUDGET_BYTES / 1e6:.0f} MB budget at scale {SCALE}"
    )


def test_csr_peak_independent_of_window_axis(peaks):
    """The O(nnz) claim itself: quadrupling n_windows must not grow the
    CSR path's peak by more than measurement noise."""
    p8, p32 = peaks[8], peaks[GATE_WINDOWS]
    assert p32 <= 1.2 * p8, (
        f"CSR peak grew {p32 / p8:.2f}x from 8 to {GATE_WINDOWS} windows — "
        "something re-densified along the window axis"
    )
