"""The one home for calls whose spelling depends on the installed JAX.

``enable_x64`` scopes 64-bit types for the packed uint64 sort key
(``core/ops.py``).  JAX 0.9 removed the experimental x64 context manager;
the supported one is ``jax.enable_x64(True)``.
"""
from __future__ import annotations

import jax

__all__ = ["enable_x64"]


def enable_x64():
    """Context manager that enables 64-bit types for its body."""
    return jax.enable_x64(True)
