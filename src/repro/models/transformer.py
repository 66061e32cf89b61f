"""Block assembly for all assigned architecture families.

A "block" is one decoder layer (or one pattern group for the VLM, which
interleaves cross-attention layers).  Blocks come in three call modes:

  - forward : full-sequence (training — no cache)
  - prefill : full-sequence, returns the layer's cache contribution
              (KV -> exact or PQ-compressed per config; recurrent state for SSM)
  - step    : single-token decode against the layer cache

Layer parameters are stacked (leading dim = n_layers or n_groups) and the model
scans over them — essential for compile time at 126 layers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import Array
from repro.core import importance as imp
from repro.core import kv_cache as kvc
from repro.core import pq as pqlib
from repro.models import layers, moe as moe_mod, rwkv6, ssm
from repro.parallel import serve_sharding as ssh


# ---------------------------------------------------------------------------
# Attention sub-layer with cache modes
# ---------------------------------------------------------------------------

def _attn_prefill(
    p: dict, x: Array, positions: Array, cfg, policy, lengths=None
) -> Tuple[Array, Any]:
  """Run attention over the full sequence AND build this layer's KV cache.

  `policy` is a `repro.core.cache_api.CachePolicy`; for the PQ policy this is
  where the paper's in-memory clustering runs: the importance weights (Eq. 1)
  come from the same q/k, and the windowed weighted k-means compresses the
  body — layer by layer, exactly the paper's "layer-wise codebook generation"
  that bounds peak memory.  `lengths` (B,) marks true prompt lengths for
  right-padded mixed batches (None -> full sequence).
  """
  scale = cfg.head_dim ** -0.5
  with jax.named_scope("qkv"):
    q, k, v = layers.attention_qkv(p, x, positions, cfg.rope_theta)
  attn = layers.chunked_attention(q, k, v, scale, causal=True,
                                  blk_q=cfg.attn_block, blk_k=cfg.attn_block)
  out = layers.attention_out(p, attn)

  with jax.named_scope(f"cache.{policy.name}"):
    w = None
    if policy.needs_weights:
      # Eq. 1 weights per (batch, kv head): queries of the kv-group, averaged.
      b, hq, s, hd = q.shape
      hkv = k.shape[1]
      g = hq // hkv
      qg = q.reshape(b, hkv, g, s, hd)[:, :, 0]         # lead query head / group
      t = policy.spec.recent
      chunk = min(cfg.attn_block, s)
      if lengths is None:
        w = jax.vmap(jax.vmap(
            lambda qq, kk: imp.attention_importance_weights(
                qq, kk, scale, t=t, chunk=chunk)))(qg, k)  # (B, Hkv, S)
      else:
        w = jax.vmap(lambda qb, kb, ln: jax.vmap(
            lambda qq, kk: imp.attention_importance_weights(
                qq, kk, scale, t=t, chunk=chunk, length=ln))(qb, kb)
        )(qg, k, lengths)
    cache = policy.prefill(k, v, w, lengths)
  return out, cache


def _attn_chunk(
    p: dict, x: Array, cache, positions: Array, cfg, kv_extent: int
) -> Tuple[Array, Any]:
  """Suffix-chunk attention: insert this chunk's K/V into an existing
  exact-store cache and attend causally at absolute positions.

  The bit-exactness contract with `_attn_prefill` (the prefix-cache on/off
  oracle): the chunk attends over the same `kv_extent` key extent the full
  prefill used (prompt capacity), with the same `blk_k` blocking, and every
  op is per-row — so row p's output here equals row p of a full prefill
  whose earlier rows produced exactly the cached prefix K/V.  Masked
  positions hold stale block payloads instead of padding activations, but
  contribute exact zeros either way.  Exact-store caches only (`policy.
  prefix_shareable`); weighted/clustered states couple positions and take
  the full-entry path instead.
  """
  scale = cfg.head_dim ** -0.5
  q, k, v = layers.attention_qkv(p, x, positions, cfg.rope_theta)
  start = positions[0, 0]
  chunk = x.shape[1]

  def insert(buf, new):
    # pad-insert-crop keeps shapes static while a dynamic start never
    # clamp-shifts: start + chunk always fits the padded extent
    pad = jnp.pad(new.astype(buf.dtype),
                  ((0, 0), (0, 0), (0, buf.shape[2] - chunk), (0, 0)))
    rolled = jnp.roll(pad, start, axis=2)
    written = jnp.arange(buf.shape[2])
    mask = ((written >= start) & (written < start + chunk))[None, None, :,
                                                            None]
    return jnp.where(mask, rolled, buf)

  k_c = insert(cache.k, k)
  v_c = insert(cache.v, v)
  attn = layers.chunked_attention(
      q, k_c[:, :, :kv_extent], v_c[:, :, :kv_extent], scale, causal=True,
      blk_q=cfg.attn_block, blk_k=cfg.attn_block, q_offset=start)
  out = layers.attention_out(p, attn)
  return out, cache._replace(k=k_c, v=v_c)


def dense_block_chunk(p: dict, x: Array, cache, positions: Array, cfg,
                      kv_extent: int) -> Tuple[Array, Any]:
  """Suffix-only prefill: one layer over a chunk of prompt rows, consuming
  the already-cached prefix as attention context (prefix sharing)."""
  h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
  attn, cache = _attn_chunk(p["attn"], h, cache, positions, cfg, kv_extent)
  if cfg.parallel_block:
    ffn, _ = _ffn_apply(p, h, cfg)
    return x + attn + ffn, cache
  x = x + attn
  h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
  ffn, _ = _ffn_apply(p, h, cfg)
  return x + ffn, cache


def _attn_qkv_step(p: dict, x: Array, lengths: Array, cfg):
  """Single-token q/k/v projection + RoPE at each row's position."""
  with jax.named_scope("qkv"):
    pos = lengths[:, None]                               # (B, 1) RoPE positions
    q = jnp.einsum("bsd,dhk->bshk", x, layers.wv(p["wq"], x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, layers.wv(p["wk"], x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, layers.wv(p["wv"], x.dtype))
    q = layers.apply_rope(q, pos, cfg.rope_theta)[:, 0]  # (B, H, hd)
    k = layers.apply_rope(k, pos, cfg.rope_theta)[:, 0]
    return q, k, v[:, 0]


def _attn_step(
    p: dict, x: Array, cache, lengths: Array, cfg, policy
) -> Tuple[Array, Any]:
  """Single-token attention against the cache.  x (B, 1, D), lengths (B,).

  Under an active shard plan (traced inside the sharded serve path's
  shard_map) the per-kv-head independence of every policy is the partition
  seam: q/k/v come out of the replicated projections full-width, each shard
  attends only its kv-head slice against its local cache shard, and an
  ordered all_gather reassembles the exact per-head context before the
  replicated `wo` projection — bit-identical to the unsharded step.  The
  seq fallback instead split-Ks the exact-store softmax across shards.
  """
  lengths = kvc.as_lengths(lengths, x.shape[0])
  q, k, v = _attn_qkv_step(p, x, lengths, cfg)
  plan = ssh.active_plan()
  with jax.named_scope(f"cache.{policy.name}"):
    if plan is None:
      attn, new_cache = policy.append_and_attend(cache, q, k, v, lengths)
    elif plan.mode == "heads":
      q_l, k_l, v_l = ssh.shard_attn_inputs(q, k, v, plan)
      attn, new_cache = policy.append_and_attend(cache, q_l, k_l, v_l,
                                                 lengths)
      attn = ssh.gather_attn_outputs(attn, plan)
    else:                                 # seq split-K (exact store only)
      attn, new_cache = ssh.seq_append_and_attend(
          cache, q, k, v, lengths, cfg.head_dim ** -0.5, plan)
  out = jnp.einsum("bhk,hkd->bd", attn.astype(x.dtype),
                   layers.wv(p["wo"], x.dtype))
  return out[:, None, :], new_cache


def _attn_step_paged(
    p: dict, x: Array, resident, pools, layer, tables, lengths: Array,
    cfg, policy
) -> Tuple[Array, Any, Any]:
  """Single-token attention reading pooled block storage in place.

  `resident`/`pools` are this layer's flattened policy-state leaves (the
  other kind None); the policy's block-native step streams pool blocks via
  the per-slot `tables` and writes only the rows this token produced — the
  dense gather->decode->scatter round trip never happens.
  """
  lengths = kvc.as_lengths(lengths, x.shape[0])
  q, k, v = _attn_qkv_step(p, x, lengths, cfg)
  plan = ssh.active_plan()
  with jax.named_scope(f"cache.{policy.name}"):
    if plan is None:
      attn, resident, pools = policy.append_and_attend_paged(
          resident, pools, layer, tables, q, k, v, lengths)
    else:
      # heads mode only: the block-native kernels are H-shape-generic, so
      # the same slice/attend/gather seam as `_attn_step` applies — each
      # shard's kernel streams its own head-slice of the pool through the
      # shared scalar-prefetched tables.  (Seq mode forces the dense program
      # at dispatch resolution; see core.decode_dispatch.resolve_for_plan.)
      q_l, k_l, v_l = ssh.shard_attn_inputs(q, k, v, plan)
      attn, resident, pools = policy.append_and_attend_paged(
          resident, pools, layer, tables, q_l, k_l, v_l, lengths)
      attn = ssh.gather_attn_outputs(attn, plan)
  out = jnp.einsum("bhk,hkd->bd", attn.astype(x.dtype),
                   layers.wv(p["wo"], x.dtype))
  return out[:, None, :], resident, pools


# ---------------------------------------------------------------------------
# Dense / MoE blocks
# ---------------------------------------------------------------------------

def dense_block_init(key, cfg) -> dict:
  ks = jax.random.split(key, 4)
  p = {
      "ln1": layers.rmsnorm_init(cfg.d_model, cfg.dtype),
      "attn": layers.attention_init(ks[0], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.head_dim, cfg.dtype),
      "ln2": layers.rmsnorm_init(cfg.d_model, cfg.dtype),
  }
  if cfg.n_experts > 0:
    p["moe"] = moe_mod.moe_init(ks[1], cfg.d_model, cfg.n_experts,
                                cfg.moe_d_ff, cfg.n_shared_experts,
                                cfg.top_k, cfg.dtype)
  else:
    p["mlp"] = layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.dtype)
  if cfg.hybrid:
    p["ssm"] = ssm.ssm_init(ks[2], cfg.d_model, cfg.ssm_d_inner,
                            cfg.ssm_state, cfg.dtype)
    p["ln_attn_out"] = layers.rmsnorm_init(cfg.d_model, cfg.dtype)
    p["ln_ssm_out"] = layers.rmsnorm_init(cfg.d_model, cfg.dtype)
  return p


def _ffn_apply(p: dict, x: Array, cfg) -> Tuple[Array, Array]:
  if cfg.n_experts > 0:
    out, aux = moe_mod.moe_ffn(p["moe"], x, cfg.top_k, cfg.n_experts,
                               cfg.capacity_factor,
                               a2a_quant=getattr(cfg, "moe_a2a_quant", False))
    return out, aux
  with jax.named_scope("mlp"):
    return layers.mlp(p["mlp"], x), jnp.asarray(0.0, jnp.float32)


def dense_block_forward(p: dict, x: Array, positions: Array, cfg
                        ) -> Tuple[Array, Array]:
  """Training forward (hybrid runs SSM branch in parallel with attention)."""
  h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
  scale = cfg.head_dim ** -0.5
  attn = layers.self_attention(p["attn"], h, positions, scale,
                               cfg.rope_theta, blk=cfg.attn_block)
  if cfg.hybrid:
    s0 = ssm.init_state(x.shape[0], cfg.ssm_d_inner, cfg.ssm_state, x.dtype)
    ssm_out, _ = ssm.ssm_forward(p["ssm"], h, s0)
    attn = 0.5 * (layers.rmsnorm(p["ln_attn_out"], attn, cfg.norm_eps)
                  + layers.rmsnorm(p["ln_ssm_out"], ssm_out, cfg.norm_eps))
  if cfg.parallel_block:
    # PaLM-style fused residual: one TP all-reduce per layer instead of two
    ffn, aux = _ffn_apply(p, h, cfg)
    return x + attn + ffn, aux
  x = x + attn
  h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
  ffn, aux = _ffn_apply(p, h, cfg)
  return x + ffn, aux


def dense_block_prefill(p: dict, x: Array, positions: Array, cfg,
                        policy, lengths=None) -> Tuple[Array, Any]:
  h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
  attn, kv_cache = _attn_prefill(p["attn"], h, positions, cfg, policy, lengths)
  if cfg.hybrid:
    s0 = ssm.init_state(x.shape[0], cfg.ssm_d_inner, cfg.ssm_state, x.dtype)
    ssm_out, ssm_state = ssm.ssm_forward(p["ssm"], h, s0)
    attn = 0.5 * (layers.rmsnorm(p["ln_attn_out"], attn, cfg.norm_eps)
                  + layers.rmsnorm(p["ln_ssm_out"], ssm_out, cfg.norm_eps))
    cache = (kv_cache, ssm_state)
  else:
    cache = kv_cache
  if cfg.parallel_block:
    ffn, _ = _ffn_apply(p, h, cfg)
    return x + attn + ffn, cache
  x = x + attn
  h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
  ffn, _ = _ffn_apply(p, h, cfg)
  return x + ffn, cache


def dense_block_step_paged(p: dict, x: Array, resident, pools, layer,
                           tables, lengths: Array, cfg, policy
                           ) -> Tuple[Array, Any, Any]:
  """One decoder layer's decode step over block-pooled KV storage.

  Mirrors `dense_block_step` exactly, with the attention sub-layer reading
  the physical block pool in place (`_attn_step_paged`).  Dense/MoE only —
  the hybrid SSM branch carries extra recurrent state and stays on the
  dense-cache path.
  """
  h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
  attn, resident, pools = _attn_step_paged(
      p["attn"], h, resident, pools, layer, tables, lengths, cfg, policy)
  if cfg.parallel_block:
    ffn, _ = _ffn_apply(p, h, cfg)
    return x + attn + ffn, resident, pools
  x = x + attn
  h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
  ffn, _ = _ffn_apply(p, h, cfg)
  return x + ffn, resident, pools


def dense_block_step(p: dict, x: Array, cache, lengths: Array, cfg,
                     policy) -> Tuple[Array, Any]:
  h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
  if cfg.hybrid:
    kv_cache, ssm_state = cache
    attn, new_kv = _attn_step(p["attn"], h, kv_cache, lengths, cfg, policy)
    ssm_out, new_ssm = ssm.ssm_step(p["ssm"], h[:, 0], ssm_state)
    attn = 0.5 * (layers.rmsnorm(p["ln_attn_out"], attn, cfg.norm_eps)
                  + layers.rmsnorm(p["ln_ssm_out"], ssm_out[:, None],
                                   cfg.norm_eps))
    new_cache = (new_kv, new_ssm)
  else:
    attn, new_cache = _attn_step(p["attn"], h, cache, lengths, cfg, policy)
  if cfg.parallel_block:
    ffn, _ = _ffn_apply(p, h, cfg)
    return x + attn + ffn, new_cache
  x = x + attn
  h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
  ffn, _ = _ffn_apply(p, h, cfg)
  return x + ffn, new_cache


# ---------------------------------------------------------------------------
# RWKV-6 block
# ---------------------------------------------------------------------------

def rwkv_block_init(key, cfg) -> dict:
  ks = jax.random.split(key, 2)
  return {
      "ln1": layers.rmsnorm_init(cfg.d_model, cfg.dtype),
      "tm": rwkv6.time_mix_init(ks[0], cfg.d_model, cfg.n_heads,
                                cfg.head_dim, cfg.dtype),
      "ln2": layers.rmsnorm_init(cfg.d_model, cfg.dtype),
      "cm": rwkv6.channel_mix_init(ks[1], cfg.d_model, cfg.d_ff, cfg.dtype),
  }


def rwkv_block_forward(p: dict, x: Array, state: rwkv6.RWKVState, cfg
                       ) -> Tuple[Array, rwkv6.RWKVState]:
  h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
  tm_out, state = rwkv6.time_mix(p["tm"], h, state, cfg.n_heads)
  x = x + tm_out
  h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
  cm_out, x_prev_ffn = rwkv6.channel_mix(p["cm"], h, state.x_prev_ffn)
  state = state._replace(x_prev_ffn=x_prev_ffn)
  return x + cm_out, state


def rwkv_block_step(p: dict, x: Array, state: rwkv6.RWKVState, cfg
                    ) -> Tuple[Array, rwkv6.RWKVState]:
  h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)           # (B, 1, D)
  tm_out, state = rwkv6.time_mix_step(p["tm"], h[:, 0], state, cfg.n_heads)
  x = x + tm_out[:, None]
  h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
  cm_out, x_prev_ffn = rwkv6.channel_mix(p["cm"], h, state.x_prev_ffn)
  state = state._replace(x_prev_ffn=x_prev_ffn)
  return x + cm_out, state


# ---------------------------------------------------------------------------
# VLM pattern group: [cross-attn layer, (period-1) self layers]
# ---------------------------------------------------------------------------

def vlm_group_init(key, cfg) -> dict:
  ks = jax.random.split(key, cfg.cross_attn_period + 1)
  self_layers = jax.vmap(lambda k_: dense_block_init(k_, cfg))(
      jnp.stack(ks[1:cfg.cross_attn_period]))
  return {
      "cross_ln": layers.rmsnorm_init(cfg.d_model, cfg.dtype),
      "cross": layers.cross_attention_init(
          ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
          cfg.dtype),
      "cross_gate": jnp.zeros((1,), jnp.float32),
      "cross_mlp_ln": layers.rmsnorm_init(cfg.d_model, cfg.dtype),
      "cross_mlp": layers.mlp_init(
          jax.random.fold_in(ks[0], 3), cfg.d_model, cfg.d_ff, cfg.dtype),
      "cross_mlp_gate": jnp.zeros((1,), jnp.float32),
      "selfs": self_layers,
  }


def _cross_layer(p: dict, x: Array, vision: Array, cfg) -> Array:
  scale = cfg.head_dim ** -0.5
  h = layers.rmsnorm(p["cross_ln"], x, cfg.norm_eps)
  attn = layers.cross_attention(p["cross"], h, vision, scale,
                                blk=cfg.attn_block)
  x = x + jnp.tanh(p["cross_gate"]).astype(x.dtype) * attn
  h = layers.rmsnorm(p["cross_mlp_ln"], x, cfg.norm_eps)
  return x + jnp.tanh(p["cross_mlp_gate"]).astype(x.dtype) * layers.mlp(
      p["cross_mlp"], h)


def _scan_selfs(p_selfs, x, fn):
  def body(carry, lp):
    y, aux = carry
    y, aux_i = fn(lp, y)
    return (y, aux + aux_i), None
  (x, aux), _ = jax.lax.scan(body, (x, jnp.asarray(0.0, jnp.float32)), p_selfs)
  return x, aux


def vlm_group_forward(p: dict, x: Array, vision: Array, positions: Array,
                      cfg) -> Tuple[Array, Array]:
  x = _cross_layer(p, x, vision, cfg)
  return _scan_selfs(
      p["selfs"], x, lambda lp, y: dense_block_forward(lp, y, positions, cfg))


def vlm_group_prefill(p: dict, x: Array, vision: Array, positions: Array,
                      cfg, policy, lengths=None) -> Tuple[Array, Any]:
  x = _cross_layer(p, x, vision, cfg)
  def body(y, lp):
    y, cache = dense_block_prefill(lp, y, positions, cfg, policy, lengths)
    return y, cache
  def scan_body(carry, lp):
    y = carry
    y, cache = body(y, lp)
    return y, cache
  x, caches = jax.lax.scan(scan_body, x, p["selfs"])
  return x, caches


def vlm_group_step(p: dict, x: Array, vision: Array, caches, lengths: Array,
                   cfg, policy) -> Tuple[Array, Any]:
  x = _cross_layer(p, x, vision, cfg)
  def scan_body(carry, inp):
    y = carry
    lp, cache = inp
    y, new_cache = dense_block_step(lp, y, cache, lengths, cfg, policy)
    return y, new_cache
  x, new_caches = jax.lax.scan(scan_body, x, (p["selfs"], caches))
  return x, new_caches
