"""Pallas TPU kernel: PQ decode attention on compressed KV (AQPIM Fig. 5 + §III-F).

TPU adaptation of the paper's intra-row indirection: the inner-product table
T[j, g, k] = <q_subvec_j, C_key[j, k]> is computed once per decode step and
*pinned in VMEM scratch* — the analogue of the paper's "lookup table resides
within a single DRAM row".  Per-token centroid-id lookups never re-touch HBM:
every index block is streamed HBM->VMEM exactly once, like the paper's "one row
activation per window".

Everything in the body is an operation Mosaic lowers at the serve widths
(K on lanes, no minor-dimension reshapes, no lane gathers):

  * the table is built on the VPU as lane-column x lane-row broadcasts,
    ``q[:, j*dsub+s] * C_key[s, j, :]`` summed over s — the subvector split
    never reshapes q;
  * the score lookup is a one-hot contraction on the MXU: for each subvector
    j, ``T[j] (g, K) . onehot(kidx[:, j]) (blk, K)^T`` selects T[j, g,
    kidx[t, j]] for every token of the block;
  * the value path is the paper's bucket-sum: ``p (g, blk) @ onehot(vidx[:,
    j]) (blk, K)`` accumulates attention mass per (subvector, centroid) in a
    VMEM scratch that is rescaled with the running max like any flash
    accumulator; the codebook contraction runs once, at the last block.

The one-hot dots run at ``Precision.HIGHEST``: the one-hot operand is exact,
so the selected table entries and bucket sums keep f32 precision instead of
the MXU's default single bf16 pass.

Softmax is fused flash-decoding style: running (max, denom) carried across
sequence blocks in VMEM scratch; the kernel emits the *body segment's*
normalized output plus (max, denom) so the wrapper can exactly combine it with
the full-precision sink and recent segments (paper §IV-A layout).

Two entry points share one block body:

  ``pq_decode_attention_kernel``        dense index buffers (BH, N, m) — the
                                        contiguous-layout serve path and the
                                        kernel-parity oracle target;
  ``pq_decode_attention_paged_kernel``  *block-table-native*: index pages live
                                        in the paged layout's physical pool
                                        (P+1, L, H, block, m) and the sequence
                                        -block grid axis streams block j of
                                        request bh straight from pool block
                                        ``table[bh, j]`` via a scalar-prefetched
                                        per-slot block table (+ a prefetched
                                        layer index, so the pool never gets
                                        sliced or gathered in HBM).  Zero dense
                                        materialization: the only HBM reads are
                                        the mapped blocks themselves.

Grid: (batch*kv_heads, sequence_blocks) — both sequential ("arbitrary") so scratch
accumulators carry across the sequence axis; the batch*head axis revisits scratch
from a clean @pl.when(j == 0) init.

Codebooks arrive as (BH, dsub, m, K): K on lanes, so no buffer pads a dsub-wide
minor axis out to 128 lanes.  VMEM per grid cell at m=32, K=512, g=8, f32:
  T (m, g, K) + bucket sums (m, g, K)          1.0 MiB
  codebooks 2 inputs x 2 buffers x (dsub, m, K) 0.5 MiB at dsub=2
  index blocks 2 x 2 x (blk, m), lane-padded   1.0 MiB at blk=512 (int32)
  one-hot temporaries (blk, K) f32             1.0 MiB at blk=512
  total                                        ~3.5 MiB, under the 16 MiB
                                               default scoped VMEM of v5e
(The paged variant streams layout-sized blocks — typically 16 tokens — so its
index and one-hot terms are smaller still.)
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _init_scratch(q_ref, kcb_ref, t_ref, bsum_ref, m_ref, l_ref, scale):
  """Steps 1-2 (paper): subvector split + inner-product table, once per step."""
  dsub, m = kcb_ref.shape[1], kcb_ref.shape[2]
  q = q_ref[0].astype(jnp.float32)                    # (g, d)
  for j in range(m):
    t = None
    for s in range(dsub):
      c = j * dsub + s
      term = q[:, c:c + 1] * kcb_ref[0, s, j:j + 1, :].astype(jnp.float32)
      t = term if t is None else t + term             # (g, K)
    t_ref[j] = t * scale
  bsum_ref[...] = jnp.zeros(bsum_ref.shape, jnp.float32)
  m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
  l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)


def _accumulate_block(kidx, vidx, valid, t_ref, bsum_ref, m_ref, l_ref):
  """Steps 3-7 (paper) for one sequence block.

  kidx/vidx (blk, m) int32; valid (blk,) bool.
  """
  m, _, k_cent = t_ref.shape
  blk = kidx.shape[0]
  cent = jax.lax.broadcasted_iota(jnp.int32, (blk, k_cent), 1)

  # Step 3-4 (paper): score lookup from the VMEM-resident table.
  s = None
  for j in range(m):
    onehot = (kidx[:, j:j + 1] == cent).astype(jnp.float32)   # (blk, K)
    sj = jax.lax.dot_general(
        t_ref[j], onehot, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)  # (g, blk)
    s = sj if s is None else s + sj
  s = jnp.where(valid[None, :], s, NEG_INF)

  # Step 5 (paper): fused online softmax.
  m_prev = m_ref[...]                                 # (g, 1)
  mu = jnp.max(s, axis=-1, keepdims=True)
  m_new = jnp.maximum(m_prev, mu)
  alpha = jnp.exp(m_prev - m_new)                     # (g, 1)
  p = jnp.exp(s - m_new)                              # (g, blk)
  p = jnp.where(valid[None, :], p, 0.0)
  l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
  m_ref[...] = m_new

  # Step 6 (paper): bucket-sum of attention mass per (subvector, centroid).
  for j in range(m):
    onehot = (vidx[:, j:j + 1] == cent).astype(jnp.float32)   # (blk, K)
    bsum_ref[j] = alpha * bsum_ref[j] + jax.lax.dot_general(
        p, onehot, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)  # (g, K)


def _finalize(out_ref, stats_ref, vcb_ref, bsum_ref, m_ref, l_ref):
  """Step 7 (paper): bucket sums x value codebook, then normalize.

  Output channel c = (j, s) is a lane reduction of bucket row j against the
  codebook row C_value[s, j, :]; each lands in its lane through a select, so
  the (g, d) output is assembled without a minor-dimension reshape.
  """
  dsub, m = vcb_ref.shape[1], vcb_ref.shape[2]
  g, d = out_ref.shape[1], out_ref.shape[2]
  lane = jax.lax.broadcasted_iota(jnp.int32, (g, d), 1)
  out = jnp.zeros((g, d), jnp.float32)
  for j in range(m):
    b = bsum_ref[j]                                   # (g, K)
    for s in range(dsub):
      col = jnp.sum(b * vcb_ref[0, s, j:j + 1, :].astype(jnp.float32),
                    axis=-1, keepdims=True)           # (g, 1)
      out = jnp.where(lane == j * dsub + s, col, out)
  l = l_ref[...]
  out_ref[0] = (out / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)
  stats_ref[0, :, 0:1] = m_ref[...]
  stats_ref[0, :, 1:2] = l


def _scratch(m, g, k_cent):
  return [
      pltpu.VMEM((m, g, k_cent), jnp.float32),        # inner-product table
      pltpu.VMEM((m, g, k_cent), jnp.float32),        # bucket sums
      pltpu.VMEM((g, 1), jnp.float32),                # running max
      pltpu.VMEM((g, 1), jnp.float32),                # running denom
  ]


def _pq_decode_kernel(
    # scalar prefetch
    length_ref,            # (BH,) int32 in SMEM — valid body tokens per row
    # inputs
    q_ref,                 # (1, g, d)
    kcb_ref,               # (1, dsub, m, K)   key codebook, K on lanes
    vcb_ref,               # (1, dsub, m, K)   value codebook, K on lanes
    kidx_ref,              # (1, blk, m) int32
    vidx_ref,              # (1, blk, m) int32
    # outputs
    out_ref,               # (1, g, d) f32
    stats_ref,             # (1, g, 2) f32  [..., 0]=running max, [..., 1]=denom
    # scratch
    t_ref, bsum_ref, m_ref, l_ref,
    *,
    scale: float,
    blk: int,
    n_blocks: int,
):
  bh = pl.program_id(0)
  j = pl.program_id(1)

  @pl.when(j == 0)
  def _init():
    _init_scratch(q_ref, kcb_ref, t_ref, bsum_ref, m_ref, l_ref, scale)

  length = length_ref[bh]
  pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)[0]
  valid = pos < length

  @pl.when(j * blk < length)
  def _block():
    _accumulate_block(kidx_ref[0], vidx_ref[0], valid,
                      t_ref, bsum_ref, m_ref, l_ref)

  @pl.when(j == n_blocks - 1)
  def _done():
    _finalize(out_ref, stats_ref, vcb_ref, bsum_ref, m_ref, l_ref)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "blk", "interpret"),
)
def pq_decode_attention_kernel(
    q: jax.Array,               # (BH, g, d)
    key_codebook: jax.Array,    # (BH, dsub, m, K) f32
    value_codebook: jax.Array,  # (BH, dsub, m, K) f32
    key_indices: jax.Array,     # (BH, N, m) int32
    value_indices: jax.Array,   # (BH, N, m) int32
    length: jax.Array,          # (BH,) int32
    scale: float,
    blk: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
  """Returns (body_out (BH, g, d) f32, stats (BH, g, 2) f32 = [max, denom])."""
  bhn, g, d = q.shape
  _, dsub, m, k_cent = key_codebook.shape
  n = key_indices.shape[1]
  assert n % blk == 0, f"body capacity {n} must be a multiple of blk={blk}"
  n_blocks = n // blk

  kernel = functools.partial(
      _pq_decode_kernel, scale=scale, blk=blk, n_blocks=n_blocks)
  per_row = lambda bh, j, L: (bh, 0, 0)
  codebook = lambda bh, j, L: (bh, 0, 0, 0)
  index_block = lambda bh, j, L: (bh, j, 0)

  out, stats = pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1,
          grid=(bhn, n_blocks),
          in_specs=[
              pl.BlockSpec((1, g, d), per_row),
              pl.BlockSpec((1, dsub, m, k_cent), codebook),
              pl.BlockSpec((1, dsub, m, k_cent), codebook),
              pl.BlockSpec((1, blk, m), index_block),
              pl.BlockSpec((1, blk, m), index_block),
          ],
          out_specs=[
              pl.BlockSpec((1, g, d), per_row),
              pl.BlockSpec((1, g, 2), per_row),
          ],
          scratch_shapes=_scratch(m, g, k_cent),
      ),
      out_shape=[
          jax.ShapeDtypeStruct((bhn, g, d), jnp.float32),
          jax.ShapeDtypeStruct((bhn, g, 2), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary", "arbitrary")),
      interpret=interpret,
      name="pq_decode_attention",
  )(length, q, key_codebook, value_codebook, key_indices, value_indices)
  return out, stats


# ---------------------------------------------------------------------------
# Block-table-native variant (paged layout)
# ---------------------------------------------------------------------------

def _pq_decode_paged_kernel(
    # scalar prefetch
    tables_ref,            # (BH, nb) int32 — per-slot block tables
    layer_ref,             # (1,) int32 — which layer's pool plane to read
    length_ref,            # (BH,) int32 — valid body tokens per row
    # inputs
    q_ref,                 # (1, g, d)
    kcb_ref,               # (1, dsub, m, K)
    vcb_ref,               # (1, dsub, m, K)
    kidx_ref,              # (1, 1, 1, blk, m) — pool block table[bh, j]
    vidx_ref,              # (1, 1, 1, blk, m)
    # outputs
    out_ref,               # (1, g, d) f32
    stats_ref,             # (1, g, 2) f32
    # scratch
    t_ref, bsum_ref, m_ref, l_ref,
    *,
    scale: float,
    blk: int,
    n_blocks: int,
):
  bh = pl.program_id(0)
  j = pl.program_id(1)

  @pl.when(j == 0)
  def _init():
    _init_scratch(q_ref, kcb_ref, t_ref, bsum_ref, m_ref, l_ref, scale)

  length = length_ref[bh]
  pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)[0]
  valid = pos < length

  @pl.when(j * blk < length)
  def _block():
    # pool index pages store the target-hardware narrow dtype (uint8/int16);
    # widen for the one-hot compares only here, inside VMEM
    _accumulate_block(kidx_ref[0, 0, 0].astype(jnp.int32),
                      vidx_ref[0, 0, 0].astype(jnp.int32),
                      valid, t_ref, bsum_ref, m_ref, l_ref)

  @pl.when(j == n_blocks - 1)
  def _done():
    _finalize(out_ref, stats_ref, vcb_ref, bsum_ref, m_ref, l_ref)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret"),
)
def pq_decode_attention_paged_kernel(
    q: jax.Array,                 # (BH, g, d)
    key_codebook: jax.Array,      # (BH, dsub, m, K) f32
    value_codebook: jax.Array,    # (BH, dsub, m, K) f32
    key_index_pool: jax.Array,    # (P+1, L, H, blk, m) narrow int
    value_index_pool: jax.Array,  # (P+1, L, H, blk, m)
    tables: jax.Array,            # (BH, nb) int32 — logical j -> pool block
    layer: jax.Array,             # (1,) int32
    length: jax.Array,            # (BH,) int32 — valid body tokens
    scale: float,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
  """Block-table-native PQ body attention over pooled index pages.

  The sequence-block grid axis reads pool block ``tables[bh, j]`` of layer
  ``layer[0]`` directly via the scalar-prefetched index maps — the physical
  pool is an ordinary pallas_call input, never sliced, gathered, or
  densified in HBM.  Unallocated table entries point at the pool's trash
  block; their rows sit at positions >= ``length`` and are masked like any
  ragged tail.  Returns the same (normalized body out, [max, denom]) contract
  as the dense kernel, for the exact sink/recent segment combine.
  """
  bhn, g, d = q.shape
  _, dsub, m, k_cent = key_codebook.shape
  n_heads = key_index_pool.shape[2]
  blk = key_index_pool.shape[3]
  n_blocks = tables.shape[1]

  kernel = functools.partial(
      _pq_decode_paged_kernel, scale=scale, blk=blk, n_blocks=n_blocks)
  per_row = lambda bh, j, tbl, lyr, L: (bh, 0, 0)
  codebook = lambda bh, j, tbl, lyr, L: (bh, 0, 0, 0)
  pool_block = lambda bh, j, tbl, lyr, L: (
      tbl[bh, j], lyr[0], bh % n_heads, 0, 0)

  out, stats = pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=3,
          grid=(bhn, n_blocks),
          in_specs=[
              pl.BlockSpec((1, g, d), per_row),
              pl.BlockSpec((1, dsub, m, k_cent), codebook),
              pl.BlockSpec((1, dsub, m, k_cent), codebook),
              pl.BlockSpec((1, 1, 1, blk, m), pool_block),
              pl.BlockSpec((1, 1, 1, blk, m), pool_block),
          ],
          out_specs=[
              pl.BlockSpec((1, g, d), per_row),
              pl.BlockSpec((1, g, 2), per_row),
          ],
          scratch_shapes=_scratch(m, g, k_cent),
      ),
      out_shape=[
          jax.ShapeDtypeStruct((bhn, g, d), jnp.float32),
          jax.ShapeDtypeStruct((bhn, g, 2), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary", "arbitrary")),
      interpret=interpret,
      name="pq_decode_attention_paged",
  )(tables, layer, length, q, key_codebook, value_codebook,
    key_index_pool, value_index_pool)
  return out, stats
