"""Everything a run draws comes from ``--seed`` through these two makers.

A seed may be any whole number up to 2**63. ``jax.random.key`` keeps only
the low 32 bits of a large seed, so the key folds both halves in.
"""

from __future__ import annotations

import numpy as np


def key(seed: int, *path: int):
    """A JAX key for ``seed`` and a path of stream numbers."""
    import jax

    if not 0 <= seed < 2 ** 63:
        raise ValueError(f"seed {seed} is outside [0, 2**63)")
    k = jax.random.fold_in(jax.random.key(0), seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, seed >> 32)
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


def rng(seed: int, *path: int) -> np.random.Generator:
    """A host generator for ``seed`` and a path of stream numbers."""
    if not 0 <= seed < 2 ** 63:
        raise ValueError(f"seed {seed} is outside [0, 2**63)")
    return np.random.default_rng([seed, *path])


# stream numbers, one per use, so that no two uses share draws
WEIGHTS, INPUTS, PROMPTS, SAMPLE, TUNE = 1, 2, 3, 4, 5
