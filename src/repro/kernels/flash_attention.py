"""Pallas TPU kernel: blockwise causal flash attention (exact baseline).

The exact-attention hot path for prefill/training — the computation AQPIM's PQ
attention replaces during decode, and the baseline every paper figure compares
against.  Standard flash-attention-2 style forward: online softmax with running
(max, denom) in VMEM scratch, KV blocks streamed innermost, GQA handled by mapping
the query head to its KV head in the BlockSpec index_map (no KV replication in HBM).

Grid: (batch, q_heads, q_blocks, kv_blocks) — kv axis sequential (accumulators),
the rest parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, out_ref,
    acc_ref, m_ref, l_ref,
    *, scale: float, blk_q: int, blk_k: int, n_kv_blocks: int, causal: bool,
):
  i = pl.program_id(2)
  j = pl.program_id(3)

  @pl.when(j == 0)
  def _init():
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

  # skip blocks strictly above the causal diagonal
  run = (not causal) or (j * blk_k <= i * blk_q + blk_q - 1)

  @pl.when(run)
  def _block():
    q = q_ref[0, 0].astype(jnp.float32)               # (blk_q, d)
    k = k_ref[0, 0].astype(jnp.float32)               # (blk_k, d)
    v = v_ref[0, 0].astype(jnp.float32)               # (blk_k, d)
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (blk_q, blk_k)
    if causal:
      q_pos = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
      k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
      s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    mu = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, mu)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  @pl.when(j == n_kv_blocks - 1)
  def _finalize():
    out_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "blk_q", "blk_k", "interpret"))
def flash_attention_kernel(
    q: jax.Array,   # (B, Hq, N, d)
    k: jax.Array,   # (B, Hkv, N, d)
    v: jax.Array,   # (B, Hkv, N, d)
    scale: float,
    causal: bool = True,
    blk_q: int = 512,
    blk_k: int = 512,
    *,
    interpret: bool,
) -> jax.Array:
  b, hq, n, d = q.shape
  hkv = k.shape[1]
  g = hq // hkv
  assert n % blk_q == 0 and n % blk_k == 0
  n_kv_blocks = n // blk_k
  grid = (b, hq, n // blk_q, n_kv_blocks)

  return pl.pallas_call(
      functools.partial(
          _flash_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k,
          n_kv_blocks=n_kv_blocks, causal=causal),
      grid=grid,
      in_specs=[
          pl.BlockSpec((1, 1, blk_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
          pl.BlockSpec((1, 1, blk_k, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
          pl.BlockSpec((1, 1, blk_k, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
      ],
      out_specs=pl.BlockSpec((1, 1, blk_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
      out_shape=jax.ShapeDtypeStruct((b, hq, n, d), q.dtype),
      scratch_shapes=[
          pltpu.VMEM((blk_q, d), jnp.float32),
          pltpu.VMEM((blk_q, 1), jnp.float32),
          pltpu.VMEM((blk_q, 1), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
      ),
      interpret=interpret,
      name="flash_attention_fwd",
  )(q, k, v)
