"""Everything a run draws comes from ``--seed`` through these two helpers.

Seeds may exceed 32 bits; both helpers take any whole number and a tag
that keeps the streams of different uses apart.
"""
from __future__ import annotations

import zlib

import numpy as np


def _words(seed: int, tag: str) -> list[int]:
    seed %= 1 << 64
    return [seed & 0xFFFFFFFF, seed >> 32, zlib.crc32(tag.encode())]


def rng(seed: int, tag: str) -> np.random.Generator:
    return np.random.default_rng(_words(seed, tag))


def jax_key(seed: int, tag: str):
    import jax

    key = jax.random.PRNGKey(0)
    for w in _words(seed, tag):
        key = jax.random.fold_in(key, np.uint32(w))
    return key
