"""Pallas TPU kernel: flash-decode attention for the exact policy, block-table
native.

One decode step of exact attention — a (g, d) GQA query group against that kv
head's cached K/V — as a flash-decoding scan over token blocks with running
(max, denom) in VMEM scratch.  Two entry points share one block body:

  ``flash_decode_kernel``        dense per-request K/V (BH, N, d) — the
                                 contiguous-layout serve path;
  ``paged_flash_decode_kernel``  *block-table-native*: K/V live in the paged
                                 layout's physical pool (P+1, L, H, block, d)
                                 and the sequence-block grid axis streams pool
                                 block ``table[bh, j]`` of layer ``layer[0]``
                                 via scalar-prefetched index maps.  The pool
                                 is an ordinary pallas_call input — never
                                 sliced, gathered, or densified in HBM; the
                                 only HBM reads are the mapped blocks.

This is the storage/compute cooperation LoL-PIM-style systems identify as the
long-context decode bottleneck: the dense gather->decode->scatter round trip
(2x the active KV through HBM per step) collapses to block reads plus the one
inserted token row.

Unallocated table entries point at the pool's trash block; their rows sit at
positions >= the request's length and are masked like any ragged tail.

Grid: (batch*kv_heads, token_blocks), both sequential ("arbitrary") so the
(max, denom, acc) scratch carries across the token axis and is re-inited per
bh row at @pl.when(j == 0).

VMEM budget per grid cell (g<=16, d=128, blk<=512, f32):
  k/v blocks 2*(blk, d)  <= 0.5 MiB
  acc (g, d) + s (g, blk) + m/l (g, 1)  << 0.1 MiB
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import packing

NEG_INF = -1e30


def _init_scratch(g, d, acc_ref, m_ref, l_ref):
  acc_ref[...] = jnp.zeros((g, d), jnp.float32)
  m_ref[...] = jnp.full((g, 1), NEG_INF, jnp.float32)
  l_ref[...] = jnp.zeros((g, 1), jnp.float32)


def _accumulate_block(q, k, v, valid, scale, acc_ref, m_ref, l_ref):
  """One token block of flash decoding.  q (g, d); k/v (blk, d); valid (blk,)."""
  s = jax.lax.dot_general(
      q, k, dimension_numbers=(((1,), (1,)), ((), ())),
      preferred_element_type=jnp.float32) * scale     # (g, blk)
  s = jnp.where(valid[None, :], s, NEG_INF)
  m_prev = m_ref[...]
  mu = jnp.max(s, axis=-1, keepdims=True)
  m_new = jnp.maximum(m_prev, mu)
  alpha = jnp.exp(m_prev - m_new)
  p = jnp.exp(s - m_new)
  p = jnp.where(valid[None, :], p, 0.0)
  l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
  m_ref[...] = m_new
  acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
      p, v, dimension_numbers=(((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32)             # (g, d)


def _finalize(out_ref, acc_ref, l_ref):
  out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
      out_ref.dtype)


def _flash_decode_kernel(
    length_ref,            # (BH,) int32 — valid tokens (incl. inserted one)
    q_ref,                 # (1, g, d)
    k_ref,                 # (1, blk, d)
    v_ref,                 # (1, blk, d)
    out_ref,               # (1, g, d) f32
    acc_ref, m_ref, l_ref,
    *, scale: float, blk: int, n_blocks: int,
):
  bh = pl.program_id(0)
  j = pl.program_id(1)
  g, d = q_ref.shape[1], q_ref.shape[2]

  @pl.when(j == 0)
  def _init():
    _init_scratch(g, d, acc_ref, m_ref, l_ref)

  length = length_ref[bh]
  pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)[0]

  @pl.when(j * blk < length)
  def _block():
    _accumulate_block(q_ref[0].astype(jnp.float32),
                      k_ref[0].astype(jnp.float32),
                      v_ref[0].astype(jnp.float32),
                      pos < length, scale, acc_ref, m_ref, l_ref)

  @pl.when(j == n_blocks - 1)
  def _done():
    _finalize(out_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("scale", "blk", "interpret"))
def flash_decode_kernel(
    q: jax.Array,        # (BH, g, d)
    k: jax.Array,        # (BH, N, d)
    v: jax.Array,        # (BH, N, d)
    length: jax.Array,   # (BH,) int32 — valid tokens per row
    scale: float,
    blk: int = 512,
    *,
    interpret: bool,
) -> jax.Array:
  """Dense-storage flash decode: (BH, g, d) f32 attention outputs."""
  bhn, g, d = q.shape
  n = k.shape[1]
  assert n % blk == 0, f"capacity {n} must be a multiple of blk={blk}"
  n_blocks = n // blk
  kernel = functools.partial(
      _flash_decode_kernel, scale=scale, blk=blk, n_blocks=n_blocks)
  return pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1,
          grid=(bhn, n_blocks),
          in_specs=[
              pl.BlockSpec((1, g, d), lambda bh, j, L: (bh, 0, 0)),
              pl.BlockSpec((1, blk, d), lambda bh, j, L: (bh, j, 0)),
              pl.BlockSpec((1, blk, d), lambda bh, j, L: (bh, j, 0)),
          ],
          out_specs=pl.BlockSpec((1, g, d), lambda bh, j, L: (bh, 0, 0)),
          scratch_shapes=[
              pltpu.VMEM((g, d), jnp.float32),
              pltpu.VMEM((g, 1), jnp.float32),
              pltpu.VMEM((g, 1), jnp.float32),
          ],
      ),
      out_shape=jax.ShapeDtypeStruct((bhn, g, d), jnp.float32),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary", "arbitrary")),
      interpret=interpret,
      name="flash_decode",
  )(length, q, k, v)


def _paged_flash_decode_kernel(
    tables_ref,            # (BH, nb) int32 — per-slot block tables
    layer_ref,             # (1,) int32
    length_ref,            # (BH,) int32
    q_ref,                 # (1, g, d)
    k_ref,                 # (1, 1, 1, blk, d) — pool block tables[bh, j]
    v_ref,                 # (1, 1, 1, blk, d)
    out_ref,               # (1, g, d) f32
    acc_ref, m_ref, l_ref,
    *, scale: float, blk: int, n_blocks: int,
):
  bh = pl.program_id(0)
  j = pl.program_id(1)
  g, d = q_ref.shape[1], q_ref.shape[2]

  @pl.when(j == 0)
  def _init():
    _init_scratch(g, d, acc_ref, m_ref, l_ref)

  length = length_ref[bh]
  pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)[0]

  @pl.when(j * blk < length)
  def _block():
    _accumulate_block(q_ref[0].astype(jnp.float32),
                      k_ref[0, 0, 0].astype(jnp.float32),
                      v_ref[0, 0, 0].astype(jnp.float32),
                      pos < length, scale, acc_ref, m_ref, l_ref)

  @pl.when(j == n_blocks - 1)
  def _done():
    _finalize(out_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_decode_kernel(
    q: jax.Array,          # (BH, g, d)
    k_pool: jax.Array,     # (P+1, L, H, blk, d)
    v_pool: jax.Array,     # (P+1, L, H, blk, d)
    tables: jax.Array,     # (BH, nb) int32 — logical block j -> pool block
    layer: jax.Array,      # (1,) int32
    length: jax.Array,     # (BH,) int32 — valid tokens per row
    scale: float,
    interpret: bool,
) -> jax.Array:
  """Block-table-native flash decode over pooled K/V: (BH, g, d) f32."""
  bhn, g, d = q.shape
  n_heads = k_pool.shape[2]
  blk = k_pool.shape[3]
  n_blocks = tables.shape[1]
  kernel = functools.partial(
      _paged_flash_decode_kernel, scale=scale, blk=blk, n_blocks=n_blocks)

  def pool_spec():
    return pl.BlockSpec(
        (1, 1, 1, blk, d),
        lambda bh, j, tbl, lyr, L: (tbl[bh, j], lyr[0], bh % n_heads, 0, 0))

  return pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=3,
          grid=(bhn, n_blocks),
          in_specs=[
              pl.BlockSpec((1, g, d), lambda bh, j, tbl, lyr, L: (bh, 0, 0)),
              pool_spec(),
              pool_spec(),
          ],
          out_specs=pl.BlockSpec((1, g, d),
                                 lambda bh, j, tbl, lyr, L: (bh, 0, 0)),
          scratch_shapes=[
              pltpu.VMEM((g, d), jnp.float32),
              pltpu.VMEM((g, 1), jnp.float32),
              pltpu.VMEM((g, 1), jnp.float32),
          ],
      ),
      out_shape=jax.ShapeDtypeStruct((bhn, g, d), jnp.float32),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary", "arbitrary")),
      interpret=interpret,
      name="paged_flash_decode",
  )(tables, layer, length, q, k_pool, v_pool)


def _packed_paged_flash_decode_kernel(
    tables_ref,            # (BH, nb) int32 — per-slot block tables
    layer_ref,             # (1,) int32
    length_ref,            # (BH,) int32
    q_ref,                 # (1, g, d)
    kp_ref,                # (1, 1, 1, blk, dp) uint8 — packed K codes
    ks_ref,                # (1, 1, 1, blk, G) uint16 — K group scales (f16 bits)
    km_ref,                # (1, 1, 1, blk, G) uint16 — K group minima (f16 bits)
    vp_ref, vs_ref, vm_ref,
    out_ref,               # (1, g, d) f32
    acc_ref, m_ref, l_ref,
    *, scale: float, blk: int, n_blocks: int, bits: int, group: int,
):
  bh = pl.program_id(0)
  j = pl.program_id(1)
  g, d = q_ref.shape[1], q_ref.shape[2]

  @pl.when(j == 0)
  def _init():
    _init_scratch(g, d, acc_ref, m_ref, l_ref)

  length = length_ref[bh]
  pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)[0]

  @pl.when(j * blk < length)
  def _block():
    # widen the nibble pages in VMEM: the only HBM reads are the packed
    # codes + f16 headers — ~0.35x the bytes of the float block
    k = packing.dequant_page(kp_ref[0, 0, 0], ks_ref[0, 0, 0],
                             km_ref[0, 0, 0], bits=bits, group=group)
    v = packing.dequant_page(vp_ref[0, 0, 0], vs_ref[0, 0, 0],
                             vm_ref[0, 0, 0], bits=bits, group=group)
    _accumulate_block(q_ref[0].astype(jnp.float32), k, v,
                      pos < length, scale, acc_ref, m_ref, l_ref)

  @pl.when(j == n_blocks - 1)
  def _done():
    _finalize(out_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("scale", "bits", "interpret"))
def packed_paged_flash_decode_kernel(
    q: jax.Array,          # (BH, g, d)
    k_pack: jax.Array,     # (P+1, L, H, blk, d*bits/8) uint8
    k_scale: jax.Array,    # (P+1, L, H, blk, G) f16
    k_min: jax.Array,      # (P+1, L, H, blk, G) f16
    v_pack: jax.Array,
    v_scale: jax.Array,
    v_min: jax.Array,
    tables: jax.Array,     # (BH, nb) int32 — logical block j -> pool block
    layer: jax.Array,      # (1,) int32
    length: jax.Array,     # (BH,) int32 — valid tokens per row
    scale: float,
    bits: int,
    interpret: bool,
) -> jax.Array:
  """Block-table-native flash decode over *packed* pooled K/V.

  Same grid/scratch structure as `paged_flash_decode_kernel`; the two float
  pool inputs become six (codes + f16 scale/min per tensor) and each mapped
  block is bit-unpacked and dequantized in VMEM before the flash accumulate.
  """
  bhn, g, d = q.shape
  n_heads = k_pack.shape[2]
  blk = k_pack.shape[3]
  dp = k_pack.shape[4]
  n_groups = k_scale.shape[4]
  group = d // n_groups
  n_blocks = tables.shape[1]
  kernel = functools.partial(
      _packed_paged_flash_decode_kernel, scale=scale, blk=blk,
      n_blocks=n_blocks, bits=bits, group=group)

  def pool_spec(width):
    return pl.BlockSpec(
        (1, 1, 1, blk, width),
        lambda bh, j, tbl, lyr, L: (tbl[bh, j], lyr[0], bh % n_heads, 0, 0))

  return pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=3,
          grid=(bhn, n_blocks),
          in_specs=[
              pl.BlockSpec((1, g, d), lambda bh, j, tbl, lyr, L: (bh, 0, 0)),
              pool_spec(dp), pool_spec(n_groups), pool_spec(n_groups),
              pool_spec(dp), pool_spec(n_groups), pool_spec(n_groups),
          ],
          out_specs=pl.BlockSpec((1, g, d),
                                 lambda bh, j, tbl, lyr, L: (bh, 0, 0)),
          scratch_shapes=[
              pltpu.VMEM((g, d), jnp.float32),
              pltpu.VMEM((g, 1), jnp.float32),
              pltpu.VMEM((g, 1), jnp.float32),
          ],
      ),
      out_shape=jax.ShapeDtypeStruct((bhn, g, d), jnp.float32),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary", "arbitrary")),
      interpret=interpret,
      name="packed_paged_flash_decode",
  )(tables, layer, length, q, k_pack, packing.f16_bits(k_scale),
    packing.f16_bits(k_min), v_pack, packing.f16_bits(v_scale),
    packing.f16_bits(v_min))
