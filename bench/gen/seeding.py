"""Seeds of any size, as the benchmark takes them, to NumPy and JAX."""

from __future__ import annotations

import numpy as np


def host_rng(seed: int, *stream: int) -> np.random.Generator:
    """A NumPy generator for ``seed`` (any non-negative integer) and an
    optional stream number, so one run's draws never share a stream."""
    return np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [int(seed), *map(int, stream)])))


def device_key(seed: int, *stream: int):
    """A JAX PRNG key from ``seed`` of any size: two 32-bit words drawn
    from the seed's sequence, so seeds past 2**32 stay distinct."""
    import jax

    lo, hi = np.random.SeedSequence([int(seed), *map(int, stream)]) \
        .generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))
