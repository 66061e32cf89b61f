"""Pallas TPU kernel: k-means assignment step (AQPIM Table I: DC + CA).

The paper's Distance Calculation runs on BankPEs (matmul-shaped, near-bank) and
Cluster Assignment (argmin reduction) on the BufferPE.  On TPU both fuse into one
kernel: the ||x||^2 - 2 x.C^T + ||C||^2 expansion is a (blk, dsub) @ (dsub, K)
MXU matmul plus rank-1 corrections; the argmin over the K lane axis is a VPU
reduction.  Centroids for all m subvector spaces stay VMEM-resident across the
sequence sweep (they are the "codebook page").

Grid: (m, sequence_blocks); centroid block is revisited per subvector (constant
along the sequence axis), token blocks stream through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _assign_kernel(x_ref, c_ref, out_ref, *, blk: int):
  """x_ref (1, blk, dsub); c_ref (1, K, dsub); out_ref (1, blk) int32."""
  x = x_ref[0].astype(jnp.float32)                     # (blk, dsub)
  c = c_ref[0].astype(jnp.float32)                     # (K, dsub)
  cross = jax.lax.dot_general(
      x, c, dimension_numbers=(((1,), (1,)), ((), ())),
      preferred_element_type=jnp.float32)              # (blk, K) MXU
  c_sq = jnp.sum(c * c, axis=-1)                       # (K,)
  # ||x||^2 is constant per row — irrelevant for the argmin; skip it (saves VPU work)
  dist = c_sq[None, :] - 2.0 * cross                   # (blk, K)
  out_ref[0] = jnp.argmin(dist, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def kmeans_assign_kernel(
    x: jax.Array,          # (m, N, dsub)
    centroids: jax.Array,  # (m, K, dsub)
    blk: int = 1024,
    *,
    interpret: bool,
) -> jax.Array:
  """Nearest-centroid ids (m, N) int32."""
  m, n, dsub = x.shape
  _, k_cent, _ = centroids.shape
  assert n % blk == 0, f"N={n} must be a multiple of blk={blk}"
  grid = (m, n // blk)
  return pl.pallas_call(
      functools.partial(_assign_kernel, blk=blk),
      grid=grid,
      in_specs=[
          pl.BlockSpec((1, blk, dsub), lambda mi, j: (mi, j, 0)),
          pl.BlockSpec((1, k_cent, dsub), lambda mi, j: (mi, 0, 0)),
      ],
      out_specs=pl.BlockSpec((1, blk), lambda mi, j: (mi, j)),
      out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary"),
      ),
      interpret=interpret,
      name="kmeans_assign",
  )(x, centroids)
