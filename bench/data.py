"""Inputs made from the seed: keys, Wishart matrices and right-hand sides.

The matrices are the paper's Wishart family (arXiv:2401.10042, Eq. 4),
A = X^T X / m with X ~ N(0, 1)^(m x n) and m = aspect x n, made on the
device in one jitted call.  Programming keys are raw uint32[2] PRNG keys.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int, stream: int) -> np.ndarray:
    """A uint32[2] key from any whole seed (also past 32 bits)."""
    state = np.random.SeedSequence([int(seed) % (1 << 63), stream])
    return state.generate_state(2, dtype=np.uint32)


@partial(jax.jit, static_argnames=("count", "n", "aspect"))
def wishart_batch(key, count: int, n: int, aspect: int):
    m = aspect * n
    x = jax.random.normal(key, (count, m, n), jnp.float32)
    return jnp.einsum("bmi,bmj->bij", x, x,
                      precision=jax.lax.Precision.HIGHEST) / m


def split_keys(key, count: int) -> np.ndarray:
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(jax.random.split(jnp.asarray(key), count))
