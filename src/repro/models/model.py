"""Unified Model API over all architecture families.

  model = Model(cfg)
  params = model.init(key)
  loss, metrics = model.train_loss(params, batch)            # full-seq training
  logits, cache = model.prefill(params, tokens[, modal])     # builds (PQ) cache
  logits, cache = model.decode_step(params, tok, cache, length[, modal])

Layer parameters are stacked and scanned; caches are pytrees whose leaves carry a
leading layer axis, so decode scans (params_layer, cache_layer) together.  The PQ
cache path implements AQPIM end to end: importance weights + windowed weighted
k-means at prefill, encode-append + compressed-attention at decode.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import Array
from repro.configs.base import ModelConfig
from repro.core import kv_cache as kvc
from repro.models import layers, rwkv6, ssm, transformer as tfm


class Model:
  def __init__(self, cfg: ModelConfig, context_len: Optional[int] = None):
    self.cfg = cfg
    self.context_len = context_len or cfg.decode_cache_len
    # the unified KV-cache policy (core.cache_api); None for attn-free families
    self.cache_policy = cfg.make_cache_policy(self.context_len)

  # -------------------------------------------------------------------------
  # init
  # -------------------------------------------------------------------------
  def init(self, key: Array) -> Dict[str, Any]:
    cfg = self.cfg
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": layers.embed_init(k_embed, cfg.vocab_size, cfg.d_model,
                                   cfg.dtype),
        "final_norm": layers.rmsnorm_init(cfg.d_model, cfg.dtype),
        "lm_head": layers.dense_init(k_head, cfg.d_model, (cfg.vocab_size,),
                                     cfg.dtype),
    }
    if cfg.family == "ssm":
      block_init = functools.partial(tfm.rwkv_block_init, cfg=cfg)
      n_stack = cfg.n_layers
    elif cfg.family == "vlm":
      block_init = functools.partial(tfm.vlm_group_init, cfg=cfg)
      assert cfg.n_layers % cfg.cross_attn_period == 0
      n_stack = cfg.n_layers // cfg.cross_attn_period
    else:
      block_init = functools.partial(tfm.dense_block_init, cfg=cfg)
      n_stack = cfg.n_layers
    keys = jax.random.split(k_layers, n_stack)
    params["layers"] = jax.vmap(lambda k_: block_init(k_))(keys)
    if cfg.weight_quant == "int8":
      from repro.models import quantize
      params = quantize.quantize_params(params)
    return params

  # -------------------------------------------------------------------------
  # embedding / frontend stubs
  # -------------------------------------------------------------------------
  # named scopes (`embed`, per layer `qkv` / `cache.<policy>` / `mlp`,
  # `logits`) tag the compiled programs' op metadata, so a profile's device
  # ops trace back to the layer boundary that emitted them
  def _embed(self, params, tokens: Array, modal: Optional[Array]) -> Array:
    with jax.named_scope("embed"):
      x = layers.embed_lookup(params["embed"], tokens)
      if self.cfg.frontend == "audio_frames" and modal is not None:
        # EnCodec frame-embedding stub: precomputed (B, S, D) added to tokens
        x = x + modal.astype(x.dtype)
    return x

  def _logits(self, params, x: Array) -> Array:
    with jax.named_scope("logits"):
      x = layers.rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
      return jnp.einsum("...d,dv->...v", x,
                        layers.wv(params["lm_head"], x.dtype))

  # -------------------------------------------------------------------------
  # training forward
  # -------------------------------------------------------------------------
  @staticmethod
  def _scan_layers(body, init, stacked, unroll: bool):
    """lax.scan over stacked layer params, or a python loop when unrolled
    (roofline validation: while-loop bodies are cost-counted once by XLA)."""
    if not unroll:
      return jax.lax.scan(body, init, stacked)
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    carry = init
    ys = []
    for i in range(n):
      lp = jax.tree_util.tree_map(lambda x: x[i], stacked)
      carry, y = body(carry, lp)
      ys.append(y)
    if ys and ys[0] is not None:
      ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
      ys = None
    return carry, ys

  def forward(self, params, tokens: Array, modal: Optional[Array] = None
              ) -> Tuple[Array, Array]:
    """(B, S) tokens -> (logits (B, S, V), moe aux loss)."""
    cfg = self.cfg
    x = self._embed(params, tokens, modal)
    positions = jnp.arange(tokens.shape[1])[None, :]

    if cfg.family == "ssm":
      def body(carry, lp):
        y = carry
        state = rwkv6.init_state(y.shape[0], cfg.d_model, cfg.n_heads, y.dtype)
        fn = functools.partial(tfm.rwkv_block_forward, cfg=cfg)
        if cfg.remat:
          fn = jax.checkpoint(fn)
        y, _ = fn(lp, y, state)
        return y, None
      x, _ = self._scan_layers(body, x, params["layers"],
                               cfg.unroll_layers)
      aux = jnp.asarray(0.0, jnp.float32)
    elif cfg.family == "vlm":
      def body(carry, lp):
        y, aux = carry
        fn = functools.partial(tfm.vlm_group_forward, cfg=cfg)
        if cfg.remat:
          fn = jax.checkpoint(fn)
        y, aux_i = fn(lp, y, modal.astype(y.dtype), positions)
        return (y, aux + aux_i), None
      (x, aux), _ = self._scan_layers(
          body, (x, jnp.asarray(0.0, jnp.float32)), params["layers"],
          cfg.unroll_layers)
    else:
      def body(carry, lp):
        y, aux = carry
        if cfg.fsdp:
          y = layers.activation_constraint(y)
        fn = functools.partial(tfm.dense_block_forward, cfg=cfg)
        if cfg.remat:
          fn = jax.checkpoint(fn)
        y, aux_i = fn(lp, y, positions)
        return (y, aux + aux_i), None
      (x, aux), _ = self._scan_layers(
          body, (x, jnp.asarray(0.0, jnp.float32)), params["layers"],
          cfg.unroll_layers)

    return self._logits(params, x), aux

  def train_loss(self, params, batch: Dict[str, Array]
                 ) -> Tuple[Array, Dict[str, Array]]:
    """Causal LM loss with z-loss and MoE load-balance aux."""
    logits, aux = self.forward(params, batch["tokens"], batch.get("modal"))
    targets = batch["targets"]
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.clip(targets, 0)[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum((lse - gold) * mask) / n
    z_loss = 1e-4 * jnp.sum(jnp.square(lse) * mask) / n
    aux_loss = 0.01 * aux / max(self.cfg.n_layers, 1)
    loss = ce + z_loss + aux_loss
    return loss, {"ce": ce, "z_loss": z_loss, "aux": aux_loss,
                  "tokens": n}

  # -------------------------------------------------------------------------
  # prefill
  # -------------------------------------------------------------------------
  def prefill(self, params, tokens: Array, modal: Optional[Array] = None,
              lengths: Optional[Array] = None) -> Tuple[Array, Any]:
    """Full-context forward that also builds every layer's cache.

    PQ codebook generation happens layer by layer inside the scan — the paper's
    "layer-wise codebook generation minimizes peak memory" (§III-B).

    `lengths` (B,) marks each request's true prompt length when `tokens` is a
    right-padded mixed batch; logits are then taken at each row's last valid
    token.  None (default) means every row spans the full sequence.
    """
    cfg = self.cfg
    if lengths is not None and (cfg.family == "ssm" or cfg.hybrid):
      raise ValueError(
          "lengths-aware prefill is unsupported for recurrent state "
          "(ssm/hybrid families): the carried state would absorb the "
          "right-padding tokens")
    x = self._embed(params, tokens, modal)
    positions = jnp.arange(tokens.shape[1])[None, :]

    if cfg.family == "ssm":
      def body(y, lp):
        state = rwkv6.init_state(y.shape[0], cfg.d_model, cfg.n_heads, y.dtype)
        y, st = tfm.rwkv_block_forward(lp, y, state, cfg)
        return y, st
      x, caches = jax.lax.scan(body, x, params["layers"])
    elif cfg.family == "vlm":
      def body(y, lp):
        y, c = tfm.vlm_group_prefill(lp, y, modal.astype(y.dtype), positions,
                                     cfg, self.cache_policy, lengths)
        return y, c
      x, caches = jax.lax.scan(body, x, params["layers"])
    else:
      def body(y, lp):
        y, c = tfm.dense_block_prefill(lp, y, positions, cfg,
                                       self.cache_policy, lengths)
        return y, c
      x, caches = jax.lax.scan(body, x, params["layers"])

    if lengths is None:
      x_last = x[:, -1:]
    else:
      idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, x.shape[1] - 1)
      x_last = x[jnp.arange(x.shape[0]), idx][:, None]
    logits = self._logits(params, x_last)
    return logits[:, 0], caches

  def prefill_chunk(self, params, tokens: Array, caches, start: Array,
                    kv_extent: int) -> Tuple[Array, Any]:
    """Suffix-only prefill over a fixed-size chunk of prompt rows.

    `tokens` (B, C) are prompt positions [start, start+C); `caches` already
    hold the K/V of positions [0, start) (a shared prefix ref'd from the
    prefix index).  Inserts the chunk's K/V and returns logits for every
    chunk row — the caller picks the row of the prompt's true last token.
    `kv_extent` must equal the padded extent the full prefill attends over
    (prompt capacity): that is what makes chunked and full prefill
    bit-identical per row.  Dense family only — MoE capacity routing and
    recurrent state couple positions across the sequence.
    """
    cfg = self.cfg
    if cfg.family != "dense":
      raise ValueError(
          f"prefill_chunk supports the dense family only, got {cfg.family!r}")
    x = self._embed(params, tokens, None)
    positions = start + jnp.arange(tokens.shape[1])[None, :]

    def body(y, inp):
      lp, c = inp
      y, c = tfm.dense_block_chunk(lp, y, c, positions, cfg, kv_extent)
      return y, c
    x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
    return self._logits(params, x), new_caches

  # -------------------------------------------------------------------------
  # decode
  # -------------------------------------------------------------------------
  def decode_step(self, params, token: Array, caches, lengths: Array,
                  modal: Optional[Array] = None) -> Tuple[Array, Any]:
    """token (B,) int32; caches leading dim = layer stack; lengths (B,) int32
    per-request cached-token counts (a scalar broadcasts)."""
    cfg = self.cfg
    lengths = kvc.as_lengths(lengths, token.shape[0])
    x = self._embed(params, token[:, None], modal if cfg.frontend == "none"
                    else None)
    if cfg.frontend == "audio_frames" and modal is not None:
      x = x + modal[:, :1].astype(x.dtype)

    if cfg.family == "ssm":
      def body(y, inp):
        lp, st = inp
        y, st = tfm.rwkv_block_step(lp, y, st, cfg)
        return y, st
      x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
    elif cfg.family == "vlm":
      def body(y, inp):
        lp, c = inp
        y, c = tfm.vlm_group_step(lp, y, modal.astype(y.dtype), c, lengths,
                                  cfg, self.cache_policy)
        return y, c
      x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
    else:
      def body(y, inp):
        lp, c = inp
        y, c = tfm.dense_block_step(lp, y, c, lengths, cfg, self.cache_policy)
        return y, c
      x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))

    logits = self._logits(params, x[:, 0])
    return logits, new_caches

  def decode_step_paged(self, params, token: Array, resident_leaves,
                        pool_leaves, tables: Array, lengths: Array
                        ) -> Tuple[Array, Any, Any]:
    """Block-table-native decode step: attention reads pooled KV in place.

    `resident_leaves` is the flattened per-layer policy state (paged leaves
    None) with leading layer axis; `pool_leaves` the physical pools
    (P+1, L, ..., block, ...) shared across the layer scan (carried; each
    layer's policy step writes its new rows into them, for the exact policy
    at lane-wide head dims by the kernel's whole-page write-back in place);
    `tables` the (B, nb) per-slot block tables.  The layer counter rides the
    carry so each layer's kernel call
    addresses its own pool plane through the scalar-prefetched index maps —
    the pool is never sliced, gathered, or densified.  Dense/MoE attention
    families only (the ones the serve engine admits).
    """
    cfg = self.cfg
    if cfg.family not in ("dense", "moe") or cfg.hybrid:
      raise ValueError(
          f"decode_step_paged supports dense/moe attention, got "
          f"{cfg.family!r} (hybrid={cfg.hybrid})")
    lengths = kvc.as_lengths(lengths, token.shape[0])
    x = self._embed(params, token[:, None], None)

    def body(carry, inp):
      y, layer, pools = carry
      lp, res = inp
      y, new_res, pools = tfm.dense_block_step_paged(
          lp, y, res, pools, layer, tables, lengths, cfg, self.cache_policy)
      return (y, layer + 1, pools), new_res

    (x, _, pool_leaves), new_resident = jax.lax.scan(
        body, (x, jnp.asarray(0, jnp.int32), pool_leaves),
        (params["layers"], resident_leaves))
    logits = self._logits(params, x[:, 0])
    return logits, new_resident, pool_leaves

  # -------------------------------------------------------------------------
  # cache constructors (dry-run input specs / serving init)
  # -------------------------------------------------------------------------
  def init_cache(self, batch: int) -> Any:
    """Zero cache at full context capacity (decode-shape dry-runs)."""
    cfg = self.cfg
    n_stack = (cfg.n_layers if cfg.family != "vlm"
               else cfg.n_layers // cfg.cross_attn_period)

    def one_layer_kv():
      return self.cache_policy.init(batch, cfg.n_kv_heads, cfg.head_dim)

    def stack(tree, n):
      return jax.tree_util.tree_map(
          lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)

    if cfg.family == "ssm":
      st = rwkv6.init_state(batch, cfg.d_model, cfg.n_heads, cfg.dtype)
      return stack(st, n_stack)
    if cfg.family == "vlm":
      inner = stack(one_layer_kv(), cfg.cross_attn_period - 1)
      return stack(inner, n_stack)
    if cfg.hybrid:
      pair = (one_layer_kv(),
              ssm.init_state(batch, cfg.ssm_d_inner, cfg.ssm_state, cfg.dtype))
      return stack(pair, n_stack)
    return stack(one_layer_kv(), n_stack)
