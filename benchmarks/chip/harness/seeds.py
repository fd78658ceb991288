"""Every random draw of a run comes from --seed through one named stream."""
from __future__ import annotations

import zlib

import numpy as np

_MASK = (1 << 64) - 1


def _seq(seed: int, stream: str) -> np.random.SeedSequence:
    return np.random.SeedSequence(entropy=int(seed) & _MASK,
                                  spawn_key=(zlib.crc32(stream.encode()),))


def rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of one seed."""
    return np.random.default_rng(_seq(seed, stream))


def jax_key(seed: int, stream: str):
    """A JAX threefry key for one named stream of one seed (any int)."""
    import jax
    words = _seq(seed, stream).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")
