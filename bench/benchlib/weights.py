"""Pieces every model module (``bench/models/<reference>.py``) draws its
random weights with, from the seed, on the device and in the dtype they
are served in.

Matrices are N(0, 1/fan_in), the scale the program's own initialiser
uses, so logits come out with unit spread.  Norm gains are
1 + 0.1 N(0, 1) per channel, so the program is compared with the
reference on gains that are not all one."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, including seeds past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def gain(key, n, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(dtype)


def param_bytes(params) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
