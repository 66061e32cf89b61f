"""Sub-byte KV packing: GGUF-style block quantization + Pallas bit-unpack.

Block format (the GGUF ``Q4_1`` layout adapted to KV rows): values are
grouped along the channel axis into groups of ``group = gcd(d, 32)``; each
group stores

    scale   f16    (max - min) / (2^bits - 1)
    min     f16    group minimum
    codes   `bits`-wide unsigned codes; at 4 bits, *split-half* packed —
            byte ``j`` of a row carries code ``j`` in its low nibble and
            code ``j + d/2`` in its high nibble, so unpacking is one
            concat of (p & 0xF, p >> 4) and channel order is preserved
            without any interleave shuffle (TPU-friendly: no gathers).

q8 is the same layout with one byte per code.  q5 stores the low nibble in
the q4 split-half layout and appends a fifth-bit *mask plane* — one byte per
8 channels, LSB-first — so unpacking is the q4 unpack plus one masked-or.
Per-value cost: q4 = 0.625 B (group 32), q5 = 0.75 B, q8 = 1.125 B, vs
2 B bf16 / 4 B f32.

The quantization parameters are rounded through f16 *before* the codes are
computed, so dequantizing with the stored f16 scale/min reproduces exactly
the values the encoder targeted — the Pallas kernel body and the XLA
reference path share one dequant formula (``codes * scale + min`` in f32)
and therefore agree bit-for-bit on the reconstructed K/V.

`dequant_page` is jnp-only and shape-polymorphic: the same function widens
a uint8 nibble page inside a Pallas kernel (VMEM-resident, no HBM round
trip) and dequantizes the whole dense store on the XLA path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Resident-KV codec registry: CacheSpec.kv_resident_codec key -> code width.
#: "none" keeps the dense float store (the pre-PR8 exact policy).
RESIDENT_CODECS = {"none": 0, "q4": 4, "q5": 5, "q8": 8}


def group_size(d: int) -> int:
  """Quant-group length along the channel axis: 32, shrunk to divide d."""
  return math.gcd(d, 32)


def packed_width(d: int, bits: int) -> int:
  """Bytes one packed row of `d` values occupies (codes only)."""
  return d * bits // 8


def quantize_rows(x: jax.Array, *, bits: int, group: int):
  """x (..., d) float -> (codes uint8 (..., d), scale f16 (..., G), min f16).

  Asymmetric per-group uniform quantization.  scale/min are rounded through
  f16 first and the codes are computed against the *rounded* params, so the
  stored f16 header dequantizes the codes exactly as the encoder intended.
  A zero f16 scale (constant or sub-f16-range group) degrades to codes=0,
  dequantizing to the group minimum.
  """
  qmax = (1 << bits) - 1
  d = x.shape[-1]
  lead = x.shape[:-1]
  xg = x.astype(jnp.float32).reshape(lead + (d // group, group))
  lo = jnp.min(xg, axis=-1)
  hi = jnp.max(xg, axis=-1)
  scale = ((hi - lo) / qmax).astype(jnp.float16)
  mn = lo.astype(jnp.float16)
  s32 = scale.astype(jnp.float32)
  safe = jnp.where(s32 > 0, s32, 1.0)
  q = jnp.clip(jnp.round((xg - mn.astype(jnp.float32)[..., None])
                         / safe[..., None]), 0, qmax)
  return q.astype(jnp.uint8).reshape(lead + (d,)), scale, mn


def f16_bits(header: jax.Array) -> jax.Array:
  """f16 header -> its uint16 bit pattern (a free bitcast outside kernels).

  The packed decode kernel takes its headers in this form: v5e's vector
  unit has no f16 type, and Mosaic refuses f16 loads of header pages.
  """
  return jax.lax.bitcast_convert_type(header, jnp.uint16)


def _f16_to_f32(bits: jax.Array) -> jax.Array:
  """IEEE half bit patterns (16-bit ints) -> the f32 values they encode.

  Exact for every half value (normals, subnormals, zeros, inf/nan), in
  32-bit integer ops that lower anywhere, the kernel body included.
  """
  h = bits.astype(jnp.int32) & 0xFFFF
  sign = (h >> 15) << 31
  exp = (h >> 10) & 0x1F
  mant = h & 0x3FF
  wide = jnp.where(exp == 31, 0xFF, exp + 112)
  normal = jax.lax.bitcast_convert_type(
      sign | (wide << 23) | (mant << 13), jnp.float32)
  sub = mant.astype(jnp.float32) * 2.0 ** -24
  sub = jnp.where(sign != 0, -sub, sub)
  return jnp.where(exp == 0, sub, normal)


def _per_channel(header: jax.Array, d: int, group: int) -> jax.Array:
  """Per-group f16 header (..., G), as f16 or its bits -> f32 (..., d).

  Built from lane broadcasts and selects, not a reshape that splits the
  channel axis into (G, group): Mosaic cannot lower that shape cast, and
  this runs inside the packed decode kernel.
  """
  if header.dtype == jnp.float16:
    header = f16_bits(header)
  header = _f16_to_f32(header)
  shape = header.shape[:-1] + (d,)
  lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
  out = jnp.broadcast_to(header[..., :1], shape)
  for gi in range(1, header.shape[-1]):
    out = jnp.where(lane >= gi * group, header[..., gi:gi + 1], out)
  return out


def dequantize_rows(q: jax.Array, scale: jax.Array, mn: jax.Array,
                    *, group: int) -> jax.Array:
  """codes (..., d) int + per-group f16 params -> f32 (..., d).

  One formula for every consumer: f32(codes) * f32(scale) + f32(min).
  The params may come as f16 or as their `f16_bits`.
  """
  d = q.shape[-1]
  return (q.astype(jnp.float32) * _per_channel(scale, d, group)
          + _per_channel(mn, d, group))


def pack_u4(q: jax.Array) -> jax.Array:
  """(..., d) uint8 nibble codes -> (..., d//2) uint8, split-half layout."""
  dp = q.shape[-1] // 2
  return (q[..., :dp] | (q[..., dp:] << 4)).astype(jnp.uint8)


def unpack_u4(p: jax.Array) -> jax.Array:
  """(..., dp) uint8 -> (..., 2*dp) int32 nibble codes.

  Widened to int32 *before* the shift: sub-word vector shifts are the op
  TPUs lack — int32 is the lane-native width the VPU operates on.
  """
  pi = p.astype(jnp.int32)
  return jnp.concatenate([pi & 0xF, (pi >> 4) & 0xF], axis=-1)


def pack_u5(q: jax.Array) -> jax.Array:
  """(..., d) uint8 5-bit codes -> (..., 5*d//8) uint8.

  Low nibbles in the q4 split-half layout (d/2 bytes) followed by the
  fifth-bit mask plane: channel j's high bit lands in byte j // 8, bit
  position j % 8 (LSB-first) — d/8 bytes.  Requires d % 8 == 0.
  """
  d = q.shape[-1]
  lo = pack_u4(q & 0xF)
  hb = ((q >> 4) & 1).astype(jnp.int32).reshape(q.shape[:-1] + (d // 8, 8))
  weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32))
  hi = jnp.sum(hb * weights, axis=-1).astype(jnp.uint8)
  return jnp.concatenate([lo, hi], axis=-1)


def unpack_u5(p: jax.Array) -> jax.Array:
  """(..., 5*d//8) uint8 -> (..., d) int32 codes: q4 unpack + one masked-or."""
  d = p.shape[-1] * 8 // 5
  lo = unpack_u4(p[..., :d // 2])
  hi = p[..., d // 2:].astype(jnp.int32)
  shifts = jnp.arange(8, dtype=jnp.int32)
  bit = ((hi[..., :, None] >> shifts) & 1).reshape(p.shape[:-1] + (d,))
  return lo | (bit << 4)


def pack_rows(x: jax.Array, *, bits: int, group: int):
  """x (..., d) float -> (packed uint8 (..., d*bits/8), scale f16, min f16)."""
  q, scale, mn = quantize_rows(x, bits=bits, group=group)
  if bits == 4:
    return pack_u4(q), scale, mn
  if bits == 5:
    return pack_u5(q), scale, mn
  return q, scale, mn


def dequant_page(pack: jax.Array, scale: jax.Array, mn: jax.Array,
                 *, bits: int, group: int) -> jax.Array:
  """Packed page (..., d*bits/8) uint8 + f16 headers -> f32 values (..., d).

  jnp-only: runs identically inside a Pallas kernel body (the in-VMEM
  widen) and on the XLA reference path, which is what makes the two decode
  programs produce bit-identical attention inputs.
  """
  if bits == 4:
    q = unpack_u4(pack)
  elif bits == 5:
    q = unpack_u5(pack)
  else:
    q = pack.astype(jnp.int32)
  return dequantize_rows(q, scale, mn, group=group)


# ---------------------------------------------------------------------------
# Standalone Pallas bit-unpack primitive
# ---------------------------------------------------------------------------

def _unpack_u4_kernel(p_ref, out_ref):
  out_ref[...] = unpack_u4(p_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_u4_kernel(p: jax.Array, interpret: bool) -> jax.Array:
  """Widen a (n, dp) uint8 nibble page to (n, 2*dp) int32 codes in VMEM.

  The unit-testable core of the packed decode kernels: everything they add
  on top (dequant + flash accumulate) is ordinary f32 math.
  """
  n, dp = p.shape
  return pl.pallas_call(
      _unpack_u4_kernel,
      out_shape=jax.ShapeDtypeStruct((n, 2 * dp), jnp.int32),
      interpret=interpret,
      name="unpack_u4",
  )(p)
