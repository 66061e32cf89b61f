"""KV-cache structures: exact and PQ-compressed (AQPIM §III-A/H layout).

PQ cache layout per layer (paper §IV-A hyperparameters):

  [ sink (8 tokens, exact) | PQ body (windowed codebooks + indices) | recent (32, exact) ]

- the first `sink` tokens are kept full precision (attention sinks),
- the most recent `recent` tokens are kept full precision in a ring buffer (also
  the importance window t of Eq. 1),
- everything in between lives as per-(head, window) codebooks plus per-token
  m-subvector indices.

During decode (paper Fig. 3a): the new token's k/v enter the recent ring; the token
evicted from the ring is *encoded* (index append — paper step 3) against its
window's codebook page.  Codebooks themselves stay fixed after prefill (the paper
evaluated OnlinePQ and dropped it).  All shapes are static: every op here is
jit/pjit-safe and lowers into the multi-pod serve_step.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import Array
from repro.core import pq, pq_attention, windowed
from repro.kernels import ops as kops
from repro.kernels import packing


def as_lengths(length, b: int) -> Array:
  """Normalize a scalar length or per-request (B,) lengths to (B,) int32."""
  ln = jnp.asarray(length, jnp.int32)
  if ln.ndim == 0:
    return jnp.broadcast_to(ln, (b,))
  return ln.reshape(b)


# ---------------------------------------------------------------------------
# Block-indexed storage primitives (paged KV memory)
#
# A *paged* cache stores a token-axis leaf as fixed-size blocks in a shared
# physical pool instead of one contiguous per-request slab; a per-request
# block table maps logical token-block j -> physical pool block.  These four
# primitives are the numerical core the `core.cache_layout.PagedLayout`
# builds on; they are shape-static and vmap/jit-safe, so the gather -> decode
# -> scatter round trip lowers into one compiled step.
# ---------------------------------------------------------------------------

def blockify(x: Array, axis: int, block: int) -> Array:
  """Split token axis `axis` of a dense leaf into leading blocks.

  (..., N, ...) with N = nb*block  ->  (nb, ..., block, ...)
  """
  n = x.shape[axis]
  assert n % block == 0, f"token axis {n} not divisible by block {block}"
  x = x.reshape(x.shape[:axis] + (n // block, block) + x.shape[axis + 1:])
  return jnp.moveaxis(x, axis, 0)


def unblockify(blocks: Array, axis: int) -> Array:
  """Inverse of `blockify`: (nb, ..., block, ...) -> dense (..., N, ...)."""
  x = jnp.moveaxis(blocks, 0, axis)
  return x.reshape(x.shape[:axis] + (x.shape[axis] * x.shape[axis + 1],)
                   + x.shape[axis + 2:])


def gather_blocks(pool: Array, table: Array, axis: int) -> Array:
  """Materialize one request's dense leaf view from the physical pool.

  pool (P, ...block leaf...) indexed by table (nb,) int32 -> dense leaf whose
  token axis sits at `axis`.  Unallocated logical blocks point at the pool's
  trash block; their garbage rows land at positions >= the request's length
  and are masked inside every policy's attend path.
  """
  return unblockify(pool[table], axis)


def scatter_blocks(pool: Array, table: Array, dense: Array, axis: int) -> Array:
  """Write a request's dense leaf back into its pool blocks (inverse gather)."""
  block = pool.shape[axis + 1]
  return pool.at[table].set(blockify(dense, axis, block).astype(pool.dtype))


class PQCacheConfig(NamedTuple):
  """Static geometry of a PQ cache."""
  sink: int = 8            # exact sink tokens (paper §IV-A)
  recent: int = 32         # exact sliding-window tokens (= t of Eq. 1)
  body_capacity: int = 0   # max PQ-compressed tokens (multiple of n_windows)
  n_windows: int = 1       # codebook pages (paper: 1 suffices for long context)
  pq: pq.PQConfig = pq.PQConfig()

  @property
  def window_len(self) -> int:
    return self.body_capacity // self.n_windows

  def capacity(self) -> int:
    return self.sink + self.recent + self.body_capacity


class PQLayerCache(NamedTuple):
  """One layer's compressed KV state.  Leading dims (B, H_kv)."""
  sink_k: Array          # (B, H, S0, D)
  sink_v: Array
  recent_k: Array        # (B, H, R, D) ring buffer
  recent_v: Array
  key_codebooks: Array   # (B, H, nW, m, K, dsub) f32
  value_codebooks: Array
  key_indices: Array     # (B, H, Nb, m) int32
  value_indices: Array


class ExactLayerCache(NamedTuple):
  k: Array               # (B, H, N_max, D)
  v: Array


# ---------------------------------------------------------------------------
# Exact cache
# ---------------------------------------------------------------------------

def exact_cache_init(b: int, h: int, n_max: int, d: int, dtype) -> ExactLayerCache:
  z = jnp.zeros((b, h, n_max, d), dtype)
  return ExactLayerCache(k=z, v=z)


def exact_cache_prefill(k: Array, v: Array, n_max: int) -> ExactLayerCache:
  """k/v (B, H, N, D) -> cache padded to n_max."""
  b, h, n, d = k.shape
  pad = ((0, 0), (0, 0), (0, n_max - n), (0, 0))
  return ExactLayerCache(k=jnp.pad(k, pad), v=jnp.pad(v, pad))


def exact_insert_one(
    k_c: Array,          # (H, N, D)
    v_c: Array,
    k_new: Array,        # (H, D)
    v_new: Array,
    length: Array,       # scalar int32: tokens already cached in this row
) -> Tuple[Array, Array]:
  """Insert one token at position `length` of a single request's exact store.

  Shared by the free-function path below and the exact-family policies in
  `core.cache_api` so the insertion layout has exactly one implementation.
  """
  k_c = jax.lax.dynamic_update_slice(
      k_c, k_new[:, None, :].astype(k_c.dtype), (0, length, 0))
  v_c = jax.lax.dynamic_update_slice(
      v_c, v_new[:, None, :].astype(v_c.dtype), (0, length, 0))
  return k_c, v_c


def _exact_append_attend_one(
    k_c: Array,          # (H, N, D)
    v_c: Array,
    q: Array,            # (Hq, D)
    k_new: Array,        # (H, D)
    v_new: Array,
    length: Array,       # scalar int32: tokens already cached in this row
    scale: float,
) -> Tuple[Array, Array, Array]:
  """One request's decode step; batching is a vmap over this (per-row length)."""
  h, n_max, d = k_c.shape
  hq = q.shape[0]
  g = hq // h
  k_c, v_c = exact_insert_one(k_c, v_c, k_new, v_new, length)
  mask = jnp.arange(n_max) < (length + 1)

  qg = q.reshape(h, g, d)
  out = jax.vmap(
      lambda qq, kk, vv: pq_attention.exact_decode_attention(qq, kk, vv, mask, scale)
  )(qg, k_c, v_c)                                     # (H, g, D)
  return out.reshape(hq, d), k_c, v_c


def exact_cache_append_and_attend(
    cache: ExactLayerCache,
    q: Array,            # (B, Hq, D)
    k_new: Array,        # (B, H, D)
    v_new: Array,
    length: Array,       # scalar int32 OR (B,) per-request lengths
    scale: float,
) -> Tuple[Array, ExactLayerCache]:
  b = q.shape[0]
  lengths = as_lengths(length, b)
  out, k_c, v_c = jax.vmap(
      functools.partial(_exact_append_attend_one, scale=scale)
  )(cache.k, cache.v, q, k_new, v_new, lengths)
  return out, ExactLayerCache(k=k_c, v=v_c)


# ---------------------------------------------------------------------------
# Packed exact cache: sub-byte resident KV (kernels/packing.py block format)
# ---------------------------------------------------------------------------

class PackedExactLayerCache(NamedTuple):
  """Exact KV stored as q4/q8 block-quantized pages (kernels/packing.py).

  Token axis is 2 on every leaf, mirroring ExactLayerCache, so the paged/
  tiered layouts page this state exactly like the dense one — the pool
  blocks simply hold codes + f16 headers instead of floats.
  """
  k_pack: Array          # (B, H, N, d*bits/8) uint8 — split-half nibbles
  k_scale: Array         # (B, H, N, G) f16 — per-group scale, G = d/group
  k_min: Array           # (B, H, N, G) f16 — per-group minimum
  v_pack: Array
  v_scale: Array
  v_min: Array


def packed_exact_cache_init(b: int, h: int, n_max: int, d: int,
                            bits: int) -> PackedExactLayerCache:
  group = packing.group_size(d)
  zp = jnp.zeros((b, h, n_max, packing.packed_width(d, bits)), jnp.uint8)
  zs = jnp.zeros((b, h, n_max, d // group), jnp.float16)
  return PackedExactLayerCache(k_pack=zp, k_scale=zs, k_min=zs,
                               v_pack=zp, v_scale=zs, v_min=zs)


def packed_exact_cache_prefill(k: Array, v: Array, n_max: int,
                               bits: int) -> PackedExactLayerCache:
  """k/v (B, H, N, D) -> quantized cache padded to n_max."""
  b, h, n, d = k.shape
  group = packing.group_size(d)
  kp, ks, km = packing.pack_rows(k, bits=bits, group=group)
  vp, vs, vm = packing.pack_rows(v, bits=bits, group=group)
  pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, n_max - n), (0, 0)))
  return PackedExactLayerCache(k_pack=pad(kp), k_scale=pad(ks),
                               k_min=pad(km), v_pack=pad(vp),
                               v_scale=pad(vs), v_min=pad(vm))


def packed_exact_dequant(cache: PackedExactLayerCache,
                         bits: int) -> Tuple[Array, Array]:
  """Whole-store dequant -> (k, v) f32 (..., N, D); the XLA reference path
  (same formula the kernel applies per mapped block)."""
  d = cache.k_pack.shape[-1] * 8 // bits
  group = packing.group_size(d)
  k = packing.dequant_page(cache.k_pack, cache.k_scale, cache.k_min,
                           bits=bits, group=group)
  v = packing.dequant_page(cache.v_pack, cache.v_scale, cache.v_min,
                           bits=bits, group=group)
  return k, v


def _packed_insert_one(kp, ks, km, vp, vs, vm, k_new, v_new, length, *,
                       bits: int):
  """Quantize one token row and insert it at `length` (leaves are (H, N, x),
  k_new/v_new (H, D)) — the packed analogue of `exact_insert_one`."""
  d = k_new.shape[-1]
  group = packing.group_size(d)
  knp, kns, knm = packing.pack_rows(k_new, bits=bits, group=group)
  vnp, vns, vnm = packing.pack_rows(v_new, bits=bits, group=group)

  def ins(buf, row):
    return jax.lax.dynamic_update_slice(
        buf, row[:, None, :].astype(buf.dtype), (0, length, 0))

  return (ins(kp, knp), ins(ks, kns), ins(km, knm),
          ins(vp, vnp), ins(vs, vns), ins(vm, vnm))


def packed_exact_cache_append_and_attend(
    cache: PackedExactLayerCache,
    q: Array,            # (B, Hq, D)
    k_new: Array,        # (B, H, D)
    v_new: Array,
    length: Array,       # scalar int32 OR (B,) per-request lengths
    scale: float,
    bits: int,
    use_kernel: bool = False,
    *,
    interpret: bool,
) -> Tuple[Array, PackedExactLayerCache]:
  """Dense-storage packed decode step: quantize-insert the new row, then
  attend over the dequantized store (flash-decode kernel or masked XLA)."""
  b, hq, d = q.shape
  h = cache.k_pack.shape[1]
  g = hq // h
  lengths = as_lengths(length, b)
  leaves = jax.vmap(functools.partial(_packed_insert_one, bits=bits))(
      *cache, k_new, v_new, lengths)
  cache = PackedExactLayerCache(*leaves)
  k_c, v_c = packed_exact_dequant(cache, bits)      # (B, H, N, D) f32
  if use_kernel:
    out = kops.flash_decode(q.reshape(b, h, g, d), k_c, v_c, lengths + 1,
                            scale, interpret=interpret)
    return out.reshape(b, hq, d), cache
  n_max = k_c.shape[2]

  def one(kk, vv, qq, ln):
    mask = jnp.arange(n_max) < (ln + 1)
    qg = qq.reshape(h, g, d)
    out = jax.vmap(
        lambda qh, kh, vh: pq_attention.exact_decode_attention(
            qh, kh, vh, mask, scale))(qg, kk, vv)
    return out.reshape(hq, d)

  out = jax.vmap(one)(k_c, v_c, q, lengths)
  return out, cache


def packed_exact_cache_paged_step(
    pool_leaves,         # 6 pools, PackedExactLayerCache leaf order:
                         # (P+1, L, H, block, x) with x = dp | G | G
    layer: Array,        # scalar int32
    tables: Array,       # (B, nb) int32
    q: Array,            # (B, Hq, D)
    k_new: Array,        # (B, H, D)
    v_new: Array,
    length: Array,
    scale: float,
    bits: int,
    interpret: bool,
):
  """Block-table-native packed decode step: quantize the new row, write its
  codes + headers into the mapped pool block, attend in place through the
  packed kernel (codes are unpacked in VMEM — never densified in HBM)."""
  kp, ks, km, vp, vs, vm = pool_leaves
  b, hq, d = q.shape
  h = kp.shape[2]
  g = hq // h
  block = kp.shape[3]
  group = packing.group_size(d)
  lengths = as_lengths(length, b)
  pids = tables[jnp.arange(b), lengths // block]
  rows = lengths % block
  knp, kns, knm = packing.pack_rows(k_new, bits=bits, group=group)
  vnp, vns, vnm = packing.pack_rows(v_new, bits=bits, group=group)
  kp = kp.at[pids, layer, :, rows].set(knp.astype(kp.dtype))
  ks = ks.at[pids, layer, :, rows].set(kns.astype(ks.dtype))
  km = km.at[pids, layer, :, rows].set(knm.astype(km.dtype))
  vp = vp.at[pids, layer, :, rows].set(vnp.astype(vp.dtype))
  vs = vs.at[pids, layer, :, rows].set(vns.astype(vs.dtype))
  vm = vm.at[pids, layer, :, rows].set(vnm.astype(vm.dtype))
  out = kops.packed_paged_flash_decode(
      q.reshape(b, h, g, d), kp, ks, km, vp, vs, vm, tables, layer,
      lengths + 1, scale, bits=bits, interpret=interpret)
  return out.reshape(b, hq, d), [kp, ks, km, vp, vs, vm]


# ---------------------------------------------------------------------------
# PQ cache
# ---------------------------------------------------------------------------

def index_storage_dtype(cfg: PQCacheConfig):
  """Target-hardware index width (beyond-paper: uint8 at K<=256 halves the
  dominant decode-memory term vs int16 — EXPERIMENTS.md §Perf)."""
  return jnp.uint8 if cfg.pq.k <= 256 else jnp.int16


def pq_cache_init(
    b: int, h: int, d: int, cfg: PQCacheConfig, dtype=jnp.bfloat16
) -> PQLayerCache:
  m, k = cfg.pq.m, cfg.pq.k
  dsub = d // m
  idt = index_storage_dtype(cfg)
  return PQLayerCache(
      sink_k=jnp.zeros((b, h, cfg.sink, d), dtype),
      sink_v=jnp.zeros((b, h, cfg.sink, d), dtype),
      recent_k=jnp.zeros((b, h, cfg.recent, d), dtype),
      recent_v=jnp.zeros((b, h, cfg.recent, d), dtype),
      # bf16 codebook storage (paper: fp16 row buffers); f32 at compute sites
      key_codebooks=jnp.zeros((b, h, cfg.n_windows, m, k, dsub), jnp.bfloat16),
      value_codebooks=jnp.zeros((b, h, cfg.n_windows, m, k, dsub), jnp.bfloat16),
      # target-hardware index width: uint8 when K<=256 else int16; cast to
      # int32 only at gather sites.
      key_indices=jnp.zeros((b, h, cfg.body_capacity, m), idt),
      value_indices=jnp.zeros((b, h, cfg.body_capacity, m), idt),
  )


def _pq_prefill_one(
    k: Array,            # (H, N, D)
    v: Array,
    weights: Array,      # (H, N)
    length: Array,       # scalar int32: true prompt length (<= N)
    cfg: PQCacheConfig,
) -> PQLayerCache:
  """Per-request PQ prefill with a dynamic valid length (right-padded inputs).

  The layout invariant is the same as the static path: token p >= sink lives at
  ring slot (p - sink) % recent; body offsets are positions [sink, length-recent).
  Tokens beyond `length` (padding) are excluded from clustering via the body
  mask and never become visible: the decode-side masks derive from `length`.
  """
  h, n, d = k.shape
  s0, r, nb = cfg.sink, cfg.recent, cfg.body_capacity
  assert n >= s0 + r, f"prefill capacity {n} < sink+recent {s0 + r}"
  # static worst case (length == n): the mirror of the batched path's
  # `body exceeds capacity` assert — without it, overflow tokens would be
  # silently masked out of the body instead of raising
  assert n - s0 - r <= nb, (
      f"prefill capacity {n} can overflow body capacity {nb} (sink={s0}, "
      f"recent={r})")

  sink_k, sink_v = k[:, :s0], v[:, :s0]
  # last `recent` valid tokens -> ring slots keyed by absolute position
  start = jnp.maximum(length - r, 0)
  rec_tok_k = jax.lax.dynamic_slice(k, (0, start, 0), (h, r, d))
  rec_tok_v = jax.lax.dynamic_slice(v, (0, start, 0), (h, r, d))
  slots = (jnp.arange(r) + start - s0) % r
  recent_k = jnp.zeros((h, r, d), k.dtype).at[:, slots].set(rec_tok_k)
  recent_v = jnp.zeros((h, r, d), v.dtype).at[:, slots].set(rec_tok_v)

  # body candidates occupy positions [s0, s0+nb); clustering masked to the
  # true body [s0, length - r)
  pad = max(s0 + nb - n, 0)
  kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))[:, s0:s0 + nb]
  vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))[:, s0:s0 + nb]
  wp = jnp.pad(weights, ((0, 0), (0, pad)))[:, s0:s0 + nb]
  body_n = jnp.clip(length - s0 - r, 0, nb)
  mask = jnp.arange(nb) < body_n

  def per_head(kk, vv, ww):
    k_cb, k_idx = windowed.windowed_build_codebooks(
        kk, ww, cfg.pq, cfg.n_windows, mask=mask)
    v_cb, v_idx = windowed.windowed_build_codebooks(
        vv, ww, cfg.pq, cfg.n_windows, mask=mask)
    return k_cb, k_idx, v_cb, v_idx

  k_cb, k_idx, v_cb, v_idx = jax.vmap(per_head)(kp, vp, wp)
  idt = index_storage_dtype(cfg)
  return PQLayerCache(
      sink_k=sink_k, sink_v=sink_v,
      recent_k=recent_k, recent_v=recent_v,
      key_codebooks=k_cb.astype(jnp.bfloat16),
      value_codebooks=v_cb.astype(jnp.bfloat16),
      key_indices=k_idx.astype(idt),
      value_indices=v_idx.astype(idt),
  )


def pq_cache_prefill(
    k: Array,            # (B, H, N, D)
    v: Array,
    weights: Array,      # (B, H, N) importance weights (Eq. 1)
    cfg: PQCacheConfig,
    length: Optional[Array] = None,   # (B,) per-request lengths (None -> N)
) -> PQLayerCache:
  """Compress a prefilled KV into the PQ cache (paper Fig. 3a prefill step 3).

  Body tokens are positions [sink, N - recent); they are placed at body offsets
  [0, N - sink - recent).  The windowed clustering runs per (batch, head) — this is
  the computation the paper hides behind GPU prefill on the PIM side, and that we
  fuse into the prefill step.
  """
  b, h, n, d = k.shape
  if length is not None:
    return jax.vmap(functools.partial(_pq_prefill_one, cfg=cfg))(
        k, v, weights, as_lengths(length, b))
  s0, r, nb = cfg.sink, cfg.recent, cfg.body_capacity
  assert n >= s0 + r, f"prefill length {n} < sink+recent {s0 + r}"
  body_n = n - s0 - r
  assert body_n <= nb, f"body {body_n} exceeds capacity {nb}"

  sink_k, sink_v = k[:, :, :s0], v[:, :, :s0]
  # ring layout: token (s0 + i) lives at slot i % r; after prefill the last r
  # tokens occupy slots ((n - r - s0) + j) % r for j in [0, r)
  rec_tok_k, rec_tok_v = k[:, :, n - r:], v[:, :, n - r:]
  slots = (jnp.arange(r) + (n - r - s0)) % r
  recent_k = jnp.zeros((b, h, r, d), k.dtype).at[:, :, slots].set(rec_tok_k)
  recent_v = jnp.zeros((b, h, r, d), v.dtype).at[:, :, slots].set(rec_tok_v)

  body_k = k[:, :, s0:n - r]
  body_v = v[:, :, s0:n - r]
  body_w = weights[:, :, s0:n - r]

  # pad body to full capacity so window boundaries are static
  pad = nb - body_n
  body_k = jnp.pad(body_k, ((0, 0), (0, 0), (0, pad), (0, 0)))
  body_v = jnp.pad(body_v, ((0, 0), (0, 0), (0, pad), (0, 0)))
  body_w = jnp.pad(body_w, ((0, 0), (0, 0), (0, pad)))
  mask = jnp.arange(nb) < body_n

  def per_head(kk, vv, ww):
    k_cb, k_idx = windowed.windowed_build_codebooks(
        kk, ww, cfg.pq, cfg.n_windows, mask=mask)
    v_cb, v_idx = windowed.windowed_build_codebooks(
        vv, ww, cfg.pq, cfg.n_windows, mask=mask)
    return k_cb, k_idx, v_cb, v_idx

  k_cb, k_idx, v_cb, v_idx = jax.vmap(jax.vmap(per_head))(
      body_k, body_v, body_w)

  idt = index_storage_dtype(cfg)
  return PQLayerCache(
      sink_k=sink_k, sink_v=sink_v,
      recent_k=recent_k, recent_v=recent_v,
      key_codebooks=k_cb.astype(jnp.bfloat16),
      value_codebooks=v_cb.astype(jnp.bfloat16),
      key_indices=k_idx.astype(idt),
      value_indices=v_idx.astype(idt),
  )


class PQRingStep(NamedTuple):
  """Everything one PQ decode step changes *except* where the encoded indices
  land — shared by the dense path (scatter into the per-slot index buffer)
  and the block-native path (scatter one row into the physical pool)."""
  sink_k: Array          # (H, S0, D) updated
  sink_v: Array
  recent_k: Array        # (H, R, D) updated
  recent_v: Array
  k_idx_new: Array       # (H, m) encoded eviction (garbage when !do_evict)
  v_idx_new: Array
  ev: Array              # scalar int32 body offset being filled (clipped)
  do_evict: Array        # scalar bool
  sink_mask: Array       # (S0,)
  rec_mask: Array        # (R,)
  body_len: Array        # scalar int32 valid body tokens after this step


def _pq_ring_step_one(
    sink_k: Array,        # (H, S0, D)
    sink_v: Array,
    recent_k: Array,      # (H, R, D)
    recent_v: Array,
    key_codebooks: Array,    # (H, nW, m, K, dsub)
    value_codebooks: Array,
    k_new: Array,         # (H, D)
    v_new: Array,
    length: Array,        # scalar int32 tokens already cached (incl. prefill)
    cfg: PQCacheConfig,
) -> PQRingStep:
  """Steps 1-3 of one request's PQ decode: evict->encode, insert, masks.

  Reads and writes of the single affected ring slot use one-hot masks
  instead of dynamic slice/update: bit-identical results (selecting one row
  is exact; untouched rows pass through `where` verbatim) but elementwise
  ops where XLA-CPU would otherwise emit per-row scatter/gather kernels —
  measurably cheaper on the vmapped serve hot path.
  """
  s0, r, nb = cfg.sink, cfg.recent, cfg.body_capacity
  pos = length                                     # position of the new token

  in_sink = pos < s0
  slot = jnp.clip((pos - s0) % r, 0, r - 1)
  evict_pos = pos - s0 - r                          # body offset being filled

  # --- 1. encode the evicted ring entry into the PQ body -------------------
  do_evict = evict_pos >= 0
  ev = jnp.clip(evict_pos, 0, nb - 1)
  win_id = jnp.clip(ev // max(cfg.window_len, 1), 0, cfg.n_windows - 1)

  rsel = (jnp.arange(r) == slot)[None, :, None]               # (1, R, 1)
  old_k = jnp.sum(jnp.where(rsel, recent_k.astype(jnp.float32), 0.0),
                  axis=1)                                     # (H, D)
  old_v = jnp.sum(jnp.where(rsel, recent_v.astype(jnp.float32), 0.0),
                  axis=1)

  if cfg.n_windows == 1:
    # single codebook page (the paper's long-context setting): the page is
    # statically known, so skip windowed_encode's per-token page gather
    def encode_one(x, cbs):
      # x (D,), cbs (1, m, K, dsub)
      xs = x.reshape(cbs.shape[1], 1, cbs.shape[3])           # (m, 1, dsub)
      d2 = jnp.sum((cbs[0].astype(jnp.float32) - xs) ** 2, axis=-1)
      return jnp.argmin(d2, axis=-1).astype(jnp.int32)        # (m,)
  else:
    def encode_one(x, cbs):
      # x (D,), cbs (nW, m, K, dsub)
      return windowed.windowed_encode(x[None], cbs, win_id[None])[0]  # (m,)
  k_idx_new = jax.vmap(encode_one)(old_k, key_codebooks)      # (H, m)
  v_idx_new = jax.vmap(encode_one)(old_v, value_codebooks)

  # --- 2. insert the new token (sink while warming up, else ring) ----------
  sink_sel = ((jnp.arange(s0) == jnp.clip(pos, 0, s0 - 1))
              & in_sink)[None, :, None]                       # (1, S0, 1)
  ring_sel = ((jnp.arange(r) == slot) & ~in_sink)[None, :, None]

  def insert(buf, sel, val):
    return jnp.where(sel, val[:, None, :].astype(buf.dtype), buf)
  sink_k = insert(sink_k, sink_sel, k_new)
  sink_v = insert(sink_v, sink_sel, v_new)
  recent_k = insert(recent_k, ring_sel, k_new)
  recent_v = insert(recent_v, ring_sel, v_new)

  # --- 3. masks after insertion --------------------------------------------
  n_tok = pos + 1
  sink_mask = jnp.arange(s0) < jnp.minimum(n_tok, s0)
  rec_count = jnp.clip(n_tok - s0, 0, r)
  rec_mask = jnp.arange(r) < rec_count          # ring fills sequentially pre-wrap
  body_len = jnp.clip(n_tok - s0 - r, 0, nb)
  return PQRingStep(
      sink_k=sink_k, sink_v=sink_v, recent_k=recent_k, recent_v=recent_v,
      k_idx_new=k_idx_new, v_idx_new=v_idx_new, ev=ev, do_evict=do_evict,
      sink_mask=sink_mask, rec_mask=rec_mask, body_len=body_len)


def _pq_append_attend_one(
    cache: PQLayerCache,  # leaves without the batch dim: (H, ...)
    q: Array,             # (Hq, D)
    k_new: Array,         # (H, D)
    v_new: Array,
    length: Array,        # scalar int32 tokens already cached (incl. prefill)
    cfg: PQCacheConfig,
    scale: float,
    value_mode: str = "bucket",
) -> Tuple[Array, PQLayerCache]:
  hq, d = q.shape
  h = cache.recent_k.shape[0]
  g = hq // h
  nb = cfg.body_capacity

  step = _pq_ring_step_one(
      cache.sink_k, cache.sink_v, cache.recent_k, cache.recent_v,
      cache.key_codebooks, cache.value_codebooks, k_new, v_new, length, cfg)

  # one-hot masked row write (no scatter kernel; bit-identical)
  ev_sel = ((jnp.arange(nb) == step.ev) & step.do_evict)[None, :, None]

  def maybe_scatter(idx_store, idx_new):
    return jnp.where(ev_sel, idx_new[:, None, :].astype(idx_store.dtype),
                     idx_store)
  key_indices = maybe_scatter(cache.key_indices, step.k_idx_new)
  value_indices = maybe_scatter(cache.value_indices, step.v_idx_new)

  sink_k, sink_v = step.sink_k, step.sink_v
  recent_k, recent_v = step.recent_k, step.recent_v
  sink_mask, rec_mask = step.sink_mask, step.rec_mask
  body_mask = jnp.arange(nb) < step.body_len

  # --- 4. PQ attention on compressed context -------------------------------
  qg = q.reshape(h, g, d)

  def attend(qq, sk, sv, rk, rv, kcb, vcb, kix, vix):
    seg = pq_attention.PQAttnSegments(
        sink_k=sk, sink_v=sv, sink_mask=sink_mask,
        key_codebook=kcb if cfg.n_windows > 1 else kcb[0],
        value_codebook=vcb if cfg.n_windows > 1 else vcb[0],
        key_indices=kix, value_indices=vix, body_mask=body_mask,
        recent_k=rk, recent_v=rv, recent_mask=rec_mask)
    return pq_attention.pq_decode_attention(qq, seg, scale,
                                            value_mode=value_mode)

  out = jax.vmap(attend)(
      qg, sink_k, sink_v, recent_k, recent_v,
      cache.key_codebooks, cache.value_codebooks,
      key_indices, value_indices)                  # (H, g, D)

  new_cache = PQLayerCache(
      sink_k=sink_k, sink_v=sink_v, recent_k=recent_k, recent_v=recent_v,
      key_codebooks=cache.key_codebooks, value_codebooks=cache.value_codebooks,
      key_indices=key_indices, value_indices=value_indices)
  return out.reshape(hq, d), new_cache


def pq_cache_append_and_attend(
    cache: PQLayerCache,
    q: Array,            # (B, Hq, D)
    k_new: Array,        # (B, H, D)
    v_new: Array,
    length: Array,       # scalar int32 OR (B,) per-request lengths
    cfg: PQCacheConfig,
    scale: float,
    value_mode: str = "bucket",
) -> Tuple[Array, PQLayerCache]:
  """One decode step: insert token, evict->encode, attend on compressed context.

  Mirrors paper Fig. 3a decode: (3) append indices, (4) PQ attention.  Batched
  as a vmap over the per-request core so each row may sit at a different
  position in its ring/body (continuous batching).
  """
  b = q.shape[0]
  lengths = as_lengths(length, b)
  return jax.vmap(
      functools.partial(_pq_append_attend_one, cfg=cfg, scale=scale,
                        value_mode=value_mode)
  )(cache, q, k_new, v_new, lengths)


# ---------------------------------------------------------------------------
# Kernel-dispatch decode paths (core.decode_dispatch)
#
# The functions below are the Pallas-backed implementations the policies
# select when the resolved dispatch says `use_pallas`.  They compute the PQ
# body (and for exact, the whole context) through the fused kernels and the
# small exact segments (sink/recent) in pure JAX, combined exactly via
# flash-decoding (max, denom) stats — numerically, a reassociated version of
# the oracle's joint softmax (fp32 throughout).
#
# The *_paged_step variants are block-table-native: cached token state lives
# in the paged layout's physical pool (leading pool axis, then layer) and is
# read in place by the kernels through scalar-prefetched block tables; the
# only writes are the single rows this step produced.  No dense per-request
# view ever materializes in HBM — the round trip the dense gather->decode->
# scatter program pays twice per step.
# ---------------------------------------------------------------------------


def _pq_segments_combine(q, step_masks, sink_k, sink_v, recent_k, recent_v,
                         body, scale):
  """Combine kernel body stats with pure-JAX sink/recent segment stats.

  q (B, H, g, D); sink/recent (B, H, S, D); body = (out, max, denom) from the
  kernel; step_masks = (sink_mask (B, S0), rec_mask (B, R)).
  """
  sink_mask, rec_mask = step_masks

  def seg(qq, k, v, mask):
    return pq_attention.segment_attention_stats(qq, k, v, mask, scale)

  def per_req(qq, sk, sv, rk, rv, sm, rm):
    # vmap over kv heads; masks are per-request (shared across heads)
    s_out, s_m, s_l = jax.vmap(lambda a, b, c: seg(a, b, c, sm))(qq, sk, sv)
    r_out, r_m, r_l = jax.vmap(lambda a, b, c: seg(a, b, c, rm))(qq, rk, rv)
    return s_out, s_m, s_l, r_out, r_m, r_l

  s_out, s_m, s_l, r_out, r_m, r_l = jax.vmap(per_req)(
      q, sink_k, sink_v, recent_k, recent_v, sink_mask, rec_mask)
  b_out, b_m, b_l = body
  return kops.combine_attention_segments(
      [b_out, s_out, r_out], [b_m, s_m, r_m], [b_l, s_l, r_l])


def pq_cache_append_and_attend_kernel(
    cache: PQLayerCache,
    q: Array,            # (B, Hq, D)
    k_new: Array,        # (B, H, D)
    v_new: Array,
    length: Array,
    cfg: PQCacheConfig,
    scale: float,
    interpret: bool,
) -> Tuple[Array, PQLayerCache]:
  """Dense-storage PQ decode step through the Pallas body kernel.

  Same storage contract as `pq_cache_append_and_attend`; single-window
  codebooks only (the kernel pins one table page in VMEM).
  """
  assert cfg.n_windows == 1, "kernel path requires a single codebook window"
  b, hq, d = q.shape
  h = cache.recent_k.shape[1]
  g = hq // h
  lengths = as_lengths(length, b)

  step = jax.vmap(functools.partial(_pq_ring_step_one, cfg=cfg))(
      cache.sink_k, cache.sink_v, cache.recent_k, cache.recent_v,
      cache.key_codebooks, cache.value_codebooks, k_new, v_new, lengths)

  nb = cfg.body_capacity
  ev_sel = ((jnp.arange(nb)[None] == step.ev[:, None])
            & step.do_evict[:, None])[:, None, :, None]      # (B, 1, nb, 1)

  def maybe_scatter(idx_store, idx_new):
    return jnp.where(ev_sel, idx_new[:, :, None, :].astype(idx_store.dtype),
                     idx_store)
  key_indices = maybe_scatter(cache.key_indices, step.k_idx_new)
  value_indices = maybe_scatter(cache.value_indices, step.v_idx_new)

  qg = q.reshape(b, h, g, d)
  body = kops.pq_decode_attention(
      qg, cache.key_codebooks[:, :, 0], cache.value_codebooks[:, :, 0],
      key_indices, value_indices,
      jnp.broadcast_to(step.body_len[:, None], (b, h)), scale,
      blk=kops.decode_block(cfg.body_capacity), interpret=interpret)
  out = _pq_segments_combine(
      qg, (step.sink_mask, step.rec_mask), step.sink_k, step.sink_v,
      step.recent_k, step.recent_v, body, scale)

  new_cache = PQLayerCache(
      sink_k=step.sink_k, sink_v=step.sink_v,
      recent_k=step.recent_k, recent_v=step.recent_v,
      key_codebooks=cache.key_codebooks,
      value_codebooks=cache.value_codebooks,
      key_indices=key_indices, value_indices=value_indices)
  return out.reshape(b, hq, d), new_cache


def pq_cache_paged_step(
    sink_k: Array,        # (B, H, S0, D)
    sink_v: Array,
    recent_k: Array,      # (B, H, R, D)
    recent_v: Array,
    key_codebooks: Array,    # (B, H, nW, m, K, dsub)
    value_codebooks: Array,
    key_index_pool: Array,   # (P+1, L, H, block, m) narrow int
    value_index_pool: Array,
    layer: Array,         # scalar int32
    tables: Array,        # (B, nb) int32 block tables (trash = P)
    q: Array,             # (B, Hq, D)
    k_new: Array,         # (B, H, D)
    v_new: Array,
    length: Array,
    cfg: PQCacheConfig,
    scale: float,
    interpret: bool,
):
  """Block-table-native PQ decode step: pool read in place, one row written.

  Returns (out (B, Hq, D), updated rings..., updated pools...).  The evicted
  ring entry's encoded indices land directly in pool block
  ``tables[b, ev // block]`` (or the trash block when nothing evicts); the
  body kernel then streams exactly the table-mapped blocks.
  """
  assert cfg.n_windows == 1, "kernel path requires a single codebook window"
  b, hq, d = q.shape
  h = recent_k.shape[1]
  g = hq // h
  block = key_index_pool.shape[3]
  trash = key_index_pool.shape[0] - 1
  lengths = as_lengths(length, b)

  step = jax.vmap(functools.partial(_pq_ring_step_one, cfg=cfg))(
      sink_k, sink_v, recent_k, recent_v, key_codebooks, value_codebooks,
      k_new, v_new, lengths)

  # single-row pool writes: the only body-state HBM traffic this step makes.
  # Non-evicting rows aim at the trash block, whose content is never read.
  pids = jnp.where(step.do_evict,
                   tables[jnp.arange(b), step.ev // block], trash)
  rows = step.ev % block
  key_index_pool = key_index_pool.at[pids, layer, :, rows].set(
      step.k_idx_new.astype(key_index_pool.dtype))
  value_index_pool = value_index_pool.at[pids, layer, :, rows].set(
      step.v_idx_new.astype(value_index_pool.dtype))

  qg = q.reshape(b, h, g, d)
  body = kops.pq_decode_attention_paged(
      qg, key_codebooks[:, :, 0], value_codebooks[:, :, 0],
      key_index_pool, value_index_pool, tables, layer, step.body_len,
      scale, interpret=interpret)
  out = _pq_segments_combine(
      qg, (step.sink_mask, step.rec_mask), step.sink_k, step.sink_v,
      step.recent_k, step.recent_v, body, scale)
  return (out.reshape(b, hq, d), step.sink_k, step.sink_v, step.recent_k,
          step.recent_v, key_index_pool, value_index_pool)


def exact_cache_append_and_attend_kernel(
    cache: ExactLayerCache,
    q: Array,            # (B, Hq, D)
    k_new: Array,        # (B, H, D)
    v_new: Array,
    length: Array,
    scale: float,
    interpret: bool,
) -> Tuple[Array, ExactLayerCache]:
  """Dense-storage exact decode step through the flash-decode kernel."""
  b, hq, d = q.shape
  h = cache.k.shape[1]
  g = hq // h
  lengths = as_lengths(length, b)
  k_c, v_c = jax.vmap(exact_insert_one)(cache.k, cache.v, k_new, v_new,
                                        lengths)
  out = kops.flash_decode(q.reshape(b, h, g, d), k_c, v_c, lengths + 1,
                          scale, interpret=interpret)
  return out.reshape(b, hq, d), ExactLayerCache(k=k_c, v=v_c)


def exact_cache_paged_step(
    k_pool: Array,       # (P+1, L, H, block, D)
    v_pool: Array,
    layer: Array,        # scalar int32
    tables: Array,       # (B, nb) int32
    q: Array,            # (B, Hq, D)
    k_new: Array,        # (B, H, D)
    v_new: Array,
    length: Array,
    scale: float,
    interpret: bool,
):
  """Block-table-native exact decode step: insert one row, attend in place.

  The kernel writes each row's new K/V row at position `length` itself:
  for head dims that are whole lane tiles it replaces the row in the page
  it fetched and sends the whole page back by DMA, the pools aliased in
  place, so XLA never scatters into (or re-lays out) a pool; narrower head
  dims are scattered in XLA before the call (`paged_flash_decode_kernel`).
  """
  b, hq, d = q.shape
  h = k_pool.shape[2]
  g = hq // h
  lengths = as_lengths(length, b)
  out, k_pool, v_pool = kops.paged_flash_decode(
      q.reshape(b, h, g, d), k_pool, v_pool, tables, layer, lengths + 1,
      scale, interpret=interpret, new=(k_new, v_new))
  return out.reshape(b, hq, d), k_pool, v_pool


def pq_cache_bytes(cfg: PQCacheConfig, b: int, h: int, d: int) -> dict:
  """Target-hardware byte accounting (bf16 exact, fp16 codebooks, packed indices)."""
  fp = 2
  exact = (cfg.sink + cfg.recent) * d * fp * 2
  cb = cfg.n_windows * cfg.pq.m * cfg.pq.k * (d // cfg.pq.m) * fp * 2
  idx = cfg.body_capacity * cfg.pq.m * cfg.pq.index_bytes() * 2
  per_head = exact + cb + idx
  equivalent_exact = cfg.capacity() * d * fp * 2
  return dict(
      per_head_bytes=per_head,
      total_bytes=per_head * b * h,
      equivalent_exact_bytes=equivalent_exact * b * h,
      reduction_ratio=equivalent_exact / per_head,
  )
