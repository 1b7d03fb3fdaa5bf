"""Traffic generation: one general generator over data files of parameters.

A traffic mix is ``traffic/<mix>.json``.  Its ``generator`` key names a
generator in :data:`GENERATORS`; the other keys are that generator's
parameters.  A mix never carries code, so a new mix is a new data file.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from .rmat import synthetic_packets

GENERATORS = {"rmat": synthetic_packets}


def generate(traffic: dict, seed: int) -> Dict[str, np.ndarray]:
    """The packet columns of one capture drawn from ``seed``."""
    gen = GENERATORS.get(traffic["generator"])
    if gen is None:
        raise KeyError(f"unknown generator {traffic['generator']!r}; "
                       f"known: {sorted(GENERATORS)}")
    return gen(traffic["n_packets"], scale=traffic["scale"], seed=seed,
               a=traffic["a"], b=traffic["b"], c=traffic["c"])
