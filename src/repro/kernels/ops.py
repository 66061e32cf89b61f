"""Public jit'd wrappers around the Pallas kernels.

`interpret=None` auto-selects: Pallas interpret mode on CPU (this container),
compiled Mosaic on real TPU.  The model code can also bypass kernels entirely
(pure-JAX path) — see models/model.py `use_pallas` — which is what the multi-pod
dry-run lowers (XLA-fused HLO is what cost_analysis reads).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _flash
from repro.kernels import kmeans_assign as _assign
from repro.kernels import paged_flash_decode as _pfd
from repro.kernels import pq_decode as _pqd


def _auto_interpret(interpret: Optional[bool]) -> bool:
  if interpret is None:
    return jax.default_backend() != "tpu"
  return interpret


def decode_block(n: int, preferred: int = 512) -> int:
  """Largest power-of-two sequence block <= `preferred` dividing `n`.

  The decode kernels require the token capacity to split into whole blocks;
  serve-path capacities are engine-chosen (body capacity, context length), so
  the call sites pick the block instead of asserting.
  """
  blk = preferred
  while blk > 1 and n % blk:
    blk //= 2
  return max(blk, 1)


def _codebook_lanes(cb: jax.Array) -> jax.Array:
  """(B, H, m, K, dsub) codebook -> (B*H, dsub, m, K) f32, K on lanes."""
  b, h, m, k_cent, dsub = cb.shape
  return jnp.moveaxis(cb, -1, -3).reshape(b * h, dsub, m, k_cent).astype(
      jnp.float32)


def _split_stats(out, stats, b, h):
  """Kernel (BH, g, d) out + (BH, g, 2) stats -> (out, max, denom) per
  (B, H, g)."""
  g, d = out.shape[1:]
  stats = stats.reshape(b, h, g, 2)
  return out.reshape(b, h, g, d), stats[..., 0], stats[..., 1]


def pq_decode_attention(
    q: jax.Array,               # (B, H_kv, g, d)
    key_codebook: jax.Array,    # (B, H_kv, m, K, dsub)
    value_codebook: jax.Array,  # (B, H_kv, m, K, dsub)
    key_indices: jax.Array,     # (B, H_kv, N, m)
    value_indices: jax.Array,   # (B, H_kv, N, m)
    length: jax.Array,          # scalar or (B, H_kv)
    scale: float,
    blk: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
  """PQ body attention. Returns (out (B,H,g,d) f32, max (B,H,g), denom (B,H,g))."""
  b, h, g, d = q.shape
  bh = b * h
  m = key_codebook.shape[2]
  n = key_indices.shape[2]
  if jnp.ndim(length) == 0:
    length = jnp.full((bh,), length, jnp.int32)
  else:
    length = length.reshape(bh).astype(jnp.int32)
  out, stats = _pqd.pq_decode_attention_kernel(
      q.reshape(bh, g, d),
      _codebook_lanes(key_codebook), _codebook_lanes(value_codebook),
      key_indices.reshape(bh, n, m).astype(jnp.int32),
      value_indices.reshape(bh, n, m).astype(jnp.int32),
      length,
      scale=scale, blk=blk, interpret=_auto_interpret(interpret))
  return _split_stats(out, stats, b, h)


def pq_decode_attention_paged(
    q: jax.Array,               # (B, H_kv, g, d)
    key_codebook: jax.Array,    # (B, H_kv, m, K, dsub)
    value_codebook: jax.Array,  # (B, H_kv, m, K, dsub)
    key_index_pool: jax.Array,  # (P+1, L, H_kv, blk, m) narrow int
    value_index_pool: jax.Array,
    tables: jax.Array,          # (B, nb) int32 per-slot block tables
    layer: jax.Array,           # scalar int32
    length: jax.Array,          # (B,) valid body tokens
    scale: float,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
  """Block-table-native PQ body attention (zero dense materialization).

  Same return contract as `pq_decode_attention`: (out, max, denom) per
  (B, H, g) for the exact sink/recent segment combine.
  """
  b, h, g, d = q.shape
  bh = b * h
  tables_bh = jnp.repeat(tables.astype(jnp.int32), h, axis=0)   # (BH, nb)
  length_bh = jnp.repeat(length.astype(jnp.int32), h, axis=0)
  out, stats = _pqd.pq_decode_attention_paged_kernel(
      q.reshape(bh, g, d),
      _codebook_lanes(key_codebook), _codebook_lanes(value_codebook),
      key_index_pool, value_index_pool,
      tables_bh, jnp.reshape(layer, (1,)).astype(jnp.int32), length_bh,
      scale=scale, interpret=_auto_interpret(interpret))
  return _split_stats(out, stats, b, h)


def flash_decode(
    q: jax.Array,        # (B, H_kv, g, d)
    k: jax.Array,        # (B, H_kv, N, d)
    v: jax.Array,        # (B, H_kv, N, d)
    length: jax.Array,   # (B,) or (B, H_kv) valid tokens per row
    scale: float,
    blk: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
  """Dense-storage flash decode (exact policy, contiguous layout)."""
  b, h, g, d = q.shape
  bh = b * h
  n = k.shape[2]
  if jnp.ndim(length) == 1:
    length = jnp.repeat(length.astype(jnp.int32), h, axis=0)
  else:
    length = length.reshape(bh).astype(jnp.int32)
  out = _pfd.flash_decode_kernel(
      q.reshape(bh, g, d), k.reshape(bh, n, d), v.reshape(bh, n, d),
      length, scale=scale, blk=decode_block(n, min(blk, n)),
      interpret=_auto_interpret(interpret))
  return out.reshape(b, h, g, d)


def paged_flash_decode(
    q: jax.Array,        # (B, H_kv, g, d)
    k_pool: jax.Array,   # (P+1, L, H_kv, blk, d)
    v_pool: jax.Array,
    tables: jax.Array,   # (B, nb) int32
    layer: jax.Array,    # scalar int32
    length: jax.Array,   # (B,) valid tokens per row
    scale: float,
    interpret: Optional[bool] = None,
    new: Optional[Tuple[jax.Array, jax.Array]] = None,
):
  """Block-table-native flash decode over pooled K/V (exact policy).

  With `new` = (k_new, v_new) (B, H_kv, d), row `length - 1` is written
  first and (out, k_pool, v_pool) returned (`paged_flash_decode_kernel`).
  """
  return _pfd.paged_flash_decode_kernel(
      q, k_pool, v_pool, tables.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), length.astype(jnp.int32),
      scale=scale, interpret=_auto_interpret(interpret), new=new)


def packed_paged_flash_decode(
    q: jax.Array,        # (B, H_kv, g, d)
    k_pack: jax.Array,   # (P+1, L, H_kv, blk, d*bits/8) uint8
    k_scale: jax.Array,  # (P+1, L, H_kv, blk, G) f16
    k_min: jax.Array,
    v_pack: jax.Array,
    v_scale: jax.Array,
    v_min: jax.Array,
    tables: jax.Array,   # (B, nb) int32
    layer: jax.Array,    # scalar int32
    length: jax.Array,   # (B,) valid tokens per row
    scale: float,
    bits: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
  """Block-table-native flash decode over sub-byte packed pooled K/V
  (exact policy with `kv_resident_codec` q4/q8): mapped code pages are
  bit-unpacked and dequantized in VMEM, never densified in HBM."""
  b, h, g, d = q.shape
  bh = b * h
  tables_bh = jnp.repeat(tables.astype(jnp.int32), h, axis=0)
  length_bh = jnp.repeat(length.astype(jnp.int32), h, axis=0)
  out = _pfd.packed_paged_flash_decode_kernel(
      q.reshape(bh, g, d), k_pack, k_scale, k_min, v_pack, v_scale, v_min,
      tables_bh, jnp.reshape(layer, (1,)).astype(jnp.int32), length_bh,
      scale=scale, bits=bits, interpret=_auto_interpret(interpret))
  return out.reshape(b, h, g, d)


def kmeans_assign(
    x: jax.Array,          # (m, N, dsub)
    centroids: jax.Array,  # (m, K, dsub)
    blk: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
  m, n, dsub = x.shape
  pad = (-n) % blk
  if pad:
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
  out = _assign.kmeans_assign_kernel(
      x, centroids, blk=blk, interpret=_auto_interpret(interpret))
  return out[:, :n]


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    scale: float, causal: bool = True,
    blk_q: int = 512, blk_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
  n = q.shape[2]
  blk_q = min(blk_q, n)
  blk_k = min(blk_k, n)
  return _flash.flash_attention_kernel(
      q, k, v, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
      interpret=_auto_interpret(interpret))


def combine_attention_segments(outs, maxes, denoms) -> jax.Array:
  """Exact flash-decoding combine of per-segment partial attentions.

  Each segment supplies a *normalized* output plus its (running max, denom);
  combining is numerically exact: softmax over the union of segments.
  Shapes: out (..., g, d); max/denom (..., g).
  """
  m_all = functools.reduce(jnp.maximum, maxes)
  num = None
  den = None
  for o, mm, l in zip(outs, maxes, denoms):
    w = l * jnp.exp(mm - m_all)
    term = o * w[..., None]
    num = term if num is None else num + term
    den = w if den is None else den + w
  return num / jnp.maximum(den, 1e-30)[..., None]
