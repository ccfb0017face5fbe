"""Keys and generators drawn from ``--seed``.

The seed may be any whole number, larger than 32 bits hold; it is hashed
into independent 31-bit words, one per use, so that each use (weights,
traffic, order) gets its own stream and a large seed never overflows a
PRNG key.
"""

from __future__ import annotations

import numpy as np

USES = ("init", "traffic", "order", "warm")


def words(seed: int) -> dict:
    """{use: 31-bit int} for every use in ``USES``."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    w = ss.generate_state(len(USES), dtype=np.uint32) >> 1
    return {u: int(x) for u, x in zip(USES, w)}


def key(seed: int, use: str):
    import jax

    return jax.random.PRNGKey(words(seed)[use])


def rng(seed: int, use: str) -> np.random.Generator:
    return np.random.default_rng(words(seed)[use])
