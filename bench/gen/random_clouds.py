"""The paper's synthetic pairwise clouds (§III-A), made on the device.

A copy of ``repro.data.pointclouds.random_clouds`` kept with the benchmark:
A uniform in the unit cube [0, 1]^D, B uniform in it and offset by +0.1 in
every coordinate.  :func:`pool` makes a whole pool of pairs in one jitted
call, from a key that holds all 64 bits of the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

OFFSET = 0.1


def key(seed: int) -> jax.Array:
    """A raw threefry key from every bit of ``seed`` (``PRNGKey`` keeps 32)."""
    words = np.random.SeedSequence(seed % (1 << 64)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("pairs", "n", "d"))
def pool(k: jax.Array, *, pairs: int, n: int, d: int):
    """``(A, B)``, tuples of ``pairs`` (n, d) float32 clouds: pair ``i`` is
    ``(A[i], B[i])``."""
    ka, kb = jax.random.split(k)
    a = tuple(jax.random.uniform(x, (n, d), jnp.float32)
              for x in jax.random.split(ka, pairs))
    b = tuple(jax.random.uniform(x, (n, d), jnp.float32) + OFFSET
              for x in jax.random.split(kb, pairs))
    return a, b
