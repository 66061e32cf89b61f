"""What every family's weights maker shares: the key from the seed, and the
draws of one leaf.

The benchmark makes the weights, not the program, so that the reference
in `reference.py` reads weights the program did not make.  Each family
(`families/<family>.py`) draws its whole tree on the device in one jitted
call, one key a leaf, in the order its `make` lists them; `run.py` checks
the tree against the program's own before serving.  Norm scales are drawn
around 1, not set to 1, so that a norm which ignores its scale shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int) -> jax.Array:
  """A PRNG key from any non-negative seed, 64-bit ones included."""
  words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
  return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def dense(k, fan_in, shp, dtype):
  """A weight matrix with unit-variance outputs for unit-variance inputs."""
  return (jax.random.normal(k, shp, jnp.float32) * fan_in ** -0.5
          ).astype(dtype)


def scale(k, shp, dtype):
  """A norm scale around 1."""
  return (1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)).astype(dtype)


def embed(k, shp, dtype):
  return (0.02 * jax.random.normal(k, shp, jnp.float32)).astype(dtype)
