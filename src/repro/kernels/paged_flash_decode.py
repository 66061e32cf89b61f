"""Pallas TPU kernels: flash-decode attention for the exact policy, dense and
block-table native.

One decode step of exact attention — a (g, d) GQA query group against its
kv head's cached K/V — as a flash-decoding scan over token blocks with a
running (max, denom, acc) in VMEM scratch.

  ``flash_decode_kernel``        dense per-request K/V (BH, N, d) — the
                                 contiguous-layout serve path; grid
                                 (BH, N/blk), one (blk, d) block a step;
  ``paged_flash_decode_kernel``  *block-table-native*: K/V live in the paged
                                 layout's physical pool (P+1, L, H, blk, d),
                                 never sliced, gathered or densified in HBM;
                                 the only HBM reads are the pages below each
                                 row's length;
  ``packed_paged_flash_decode_kernel``  the same over sub-byte packed pages,
                                 one page of one kv head a step.

This is the storage/compute cooperation LoL-PIM-style systems identify as the
long-context decode bottleneck: the dense gather->decode->scatter round trip
(2x the active KV through HBM per step) collapses to block reads plus the one
inserted token row.

The exact policy's step hands ``paged_flash_decode_kernel`` the new K/V row
(``new``).  With hand-DMA head dims the kernel writes it: the row's last live
page, fetched for attention anyway, has the row replaced in VMEM and goes
back whole by one (H, blk, d) DMA into pools aliased input to output.  A
bf16 pool packs row pairs into 32-bit words, so neither a one-row DMA nor an
XLA row scatter fits its tiled layout: the scatter made XLA re-lay both
pools out around every layer's kernel call.  Narrower head dims keep the
XLA scatter before the call.

``paged_flash_decode_kernel`` grid: (B, ceil(nb / pps)), both sequential
("arbitrary").  A step covers ``pps`` consecutive table entries of one row
for all H kv heads (q arrives (B, H, g, d)): scores (H, g, pps*blk) are one
batched product, and the (H, g, .) f32 (max, denom, acc) scratch carries
across the row's steps.  ``pps`` follows from the shapes
(`pages_per_step`): about `STEP_TOKENS` tokens, at most nb, halved until the
step fits `STEP_VMEM_BYTES`; 16 pages at 16-token pages, so a 4096-token
table takes 16 steps a row, whatever the page size.  The last group may be
ragged.  A group at or past a row's length fetches and computes nothing;
the last live group fetches only its live pages, so pages past the length
(and the trash block unallocated entries point at) are never read.  Rows
past the length inside the last live page are masked, their values zeroed
in the f32 copy only: a written-back page returns them as they were read.
The write-back is waited on in the step that starts it, before its slot
can be refilled.  Two rows never write one page (a partial page is never
shared), except the trash block of rows of length 0, never read for a live
row.

What is fetched, by head dim:

  d a multiple of 128  the pool stays in HBM (``memory_space=pl.ANY``); each
                       live page ``pool.at[table[b, i], layer]``, one
                       contiguous (H, blk, d) slice, is DMAed by hand into a
                       (2, H, pps*blk, d) VMEM buffer, double-buffered: a
                       step starts the next live group's pages (in this row
                       or the next live one) before computing its own;
  narrower heads       Mosaic refuses DMA slices narrower than a lane tile,
                       so each page of the group is its own pipelined input
                       (1, 1, H, blk, d).  An entry past the length maps to
                       the row's last live page; the pipeline skips a fetch
                       whose page is the one that input held a step before,
                       so the trash block is never read and the row's dead
                       groups fetch at most one group of repeats.

VMEM per step (H=8, g=7, d=128, blk=16, pps=16, bf16 pool): page buffers
2 x 2 x (8, 256, 128) bf16 = 2 MiB; f32 K/V working copies 2 MiB; scores,
weights, (acc, m, l) < 0.2 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import timing
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


#: tokens one grid step of `paged_flash_decode_kernel` aims to cover: at
#: yi-34b-exact's cell shapes on a v5e a call took 0.410 ms at 128, 0.325
#: at 256 and 0.315 at 512 (twice the VMEM)
STEP_TOKENS = 256
#: fast memory one grid step's page buffers and f32 working set may take,
#: half a v5e core's 16 MiB scoped default
STEP_VMEM_BYTES = 8 * 2 ** 20
#: lanes of a vreg: a pool page is fetched by hand only when its rows are
#: a whole number of them (Mosaic refuses narrower DMA slices)
LANES = 128


def _step_vmem_bytes(pps: int, h: int, g: int, blk: int, d: int,
                     itemsize: int) -> int:
  t = pps * blk
  buffers = 2 * 2 * h * t * d * itemsize     # K and V, two slots each
  work = 2 * h * t * d * 4 + 3 * h * max(g, 8) * t * 4   # f32 K/V; s, p
  return buffers + work


def pages_per_step(nb: int, h: int, g: int, blk: int, d: int,
                   itemsize: int) -> int:
  """Pool pages one grid step fetches for every kv head of a row: about
  `STEP_TOKENS` tokens, at most the table's `nb`, halved until the step
  fits `STEP_VMEM_BYTES`.  `nb` need not be a multiple of it."""
  pps = max(1, min(nb, STEP_TOKENS // blk))
  while pps > 1 and _step_vmem_bytes(pps, h, g, blk, d,
                                     itemsize) > STEP_VMEM_BYTES:
    pps //= 2
  return pps


def _init_heads(acc_ref, m_ref, l_ref):
  acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
  m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
  l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)


def _accumulate_group(q, k, v, start, length, scale, acc_ref, m_ref, l_ref):
  """One group of pages of flash decoding, every kv head at once.

  q (H, g, d); k/v (H, t, d) holding tokens [start, start + t); rows at or
  past `length` may hold anything, NaN included.
  """
  h, t = k.shape[0], k.shape[1]
  dt = jnp.promote_types(q.dtype, k.dtype)
  s = jax.lax.dot_general(
      q.astype(dt), k.astype(dt),
      dimension_numbers=(((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32) * scale        # (H, g, t)
  valid = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) < length
  s = jnp.where(valid, s, NEG_INF)
  m_prev = m_ref[...]
  m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
  alpha = jnp.exp(m_prev - m_new)
  p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
  l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
  m_ref[...] = m_new
  # zero the rows past the length: 0 * NaN would reach the sum
  v_rows = start + jax.lax.broadcasted_iota(jnp.int32, (h, t, 1), 1)
  v = jnp.where(v_rows < length, v.astype(jnp.float32), 0.0)
  acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
      p, v, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)                 # (H, g, d)


def _paged_flash_decode_kernel(
    tables_ref,            # (B*nb,) int32 — row b's block table at b*nb
    layer_ref,             # (1,) int32
    length_ref,            # (B,) int32 — valid tokens per row
    q_ref,                 # (1, H, g, d)
    *refs,
    scale: float, blk: int, pps: int, nb: int, append: bool,
):
  """refs: [k_new, v_new (1, H, 1, d) when `append`]; k_hbm, v_hbm
  (P+1, L, H, blk, d), the pool left in HBM; out (1, H, g, d) f32;
  [k_out, v_out, the pools aliased in place, when `append`]; k_buf, v_buf
  (2, H, pps*blk, d), two slots of fetched pages; sems DMA (2, 3), [K or V,
  slot or 2 for the write-back]; state SMEM (2,), this group's slot and
  whether its DMAs started; acc (H, g, d), m, l (H, g, 1) f32."""
  if append:
    (k_new_ref, v_new_ref, k_hbm, v_hbm, out_ref, k_out, v_out,
     *scratch) = refs
  else:
    k_hbm, v_hbm, out_ref, *scratch = refs
  k_buf, v_buf, sems, state_ref, acc_ref, m_ref, l_ref = scratch
  b = pl.program_id(0)
  j = pl.program_id(1)
  n_rows = pl.num_programs(0)

  def live_pages(row):
    return jnp.minimum(pl.cdiv(length_ref[row], blk), nb)

  def for_each_page(row, group, slot, act):
    """`act` on the K and V copies of each page of `group` below the row's
    length: pages past it are never read."""
    def body(i, carry):
      pid = tables_ref[row * nb + group * pps + i]
      rows = pl.ds(pl.multiple_of(i * blk, blk), blk)
      for n, (pool, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
        act(pltpu.make_async_copy(pool.at[pid, layer_ref[0]],
                                  buf.at[slot, :, rows], sems.at[n, slot]))
      return carry
    jax.lax.fori_loop(0, jnp.minimum(pps, live_pages(row) - group * pps),
                      body, 0)

  def next_live_group():
    """(row, group) of the next grid step that fetches; row == n_rows if
    none does."""
    def next_row():
      def skip(r, nxt):
        return jnp.where((nxt == r) & (length_ref[r] <= 0), r + 1, nxt)
      return jax.lax.fori_loop(b + 1, n_rows, skip, b + 1), 0
    return jax.lax.cond((j + 1) * pps < live_pages(b),
                        lambda: (b, j + 1), next_row)

  @pl.when((b == 0) & (j == 0))
  def _reset():
    state_ref[0] = 0
    state_ref[1] = 0

  @pl.when(j == 0)
  def _init():
    _init_heads(acc_ref, m_ref, l_ref)

  @pl.when(j * pps < live_pages(b))
  def _group():
    slot = state_ref[0]

    @pl.when(state_ref[1] == 0)
    def _fetch():
      for_each_page(b, j, slot, lambda cp: cp.start())

    # start the next group's pages, in this row or the next live one,
    # before computing this group
    row, group = next_live_group()

    @pl.when(row < n_rows)
    def _prefetch():
      for_each_page(row, group, 1 - slot, lambda cp: cp.start())

    state_ref[0] = 1 - slot
    state_ref[1] = (row < n_rows).astype(jnp.int32)
    for_each_page(b, j, slot, lambda cp: cp.wait())
    if append:
      # the new row, at length - 1, lies in the row's last live page
      pos = length_ref[b] - 1
      page = jnp.minimum(pos // blk, nb - 1)
      holds_new = page < (j + 1) * pps
      rows = pl.ds(pl.multiple_of((page - j * pps) * blk, blk), blk)

      def write_back(act):
        for n, (buf, pool) in enumerate(((k_buf, k_out), (v_buf, v_out))):
          act(pltpu.make_async_copy(
              buf.at[slot, :, rows],
              pool.at[tables_ref[b * nb + page], layer_ref[0]],
              sems.at[n, 2]))

      @pl.when(holds_new)
      def _insert():
        # replace the row in the fetched page, then send the whole page
        # back: a page's rows share 32-bit words in HBM, so one row alone
        # cannot be written; rows past the length go back as they came
        at = jax.lax.broadcasted_iota(jnp.int32, k_buf.shape[1:2] + (blk,)
                                      + k_buf.shape[3:], 1) == pos % blk
        for new_ref, buf in ((k_new_ref, k_buf), (v_new_ref, v_buf)):
          buf[slot, :, rows] = jnp.where(at, new_ref[0], buf[slot, :, rows])
        write_back(lambda cp: cp.start())

    _accumulate_group(q_ref[0], k_buf[slot], v_buf[slot], j * pps * blk,
                      length_ref[b], scale, acc_ref, m_ref, l_ref)
    if append:
      # before the slot is refilled and before the kernel ends
      pl.when(holds_new)(lambda: write_back(lambda cp: cp.wait()))

  @pl.when(j == pl.num_programs(1) - 1)
  def _done():
    _finalize(out_ref, acc_ref, l_ref)


def _paged_flash_decode_pipelined_kernel(
    tables_ref, layer_ref, length_ref,
    q_ref,                 # (1, H, g, d)
    *refs,                 # pps K pages, pps V pages (1, 1, H, blk, d); out;
                           # acc, m, l as above
    scale: float, blk: int, pps: int,
):
  k_refs, v_refs = refs[:pps], refs[pps:2 * pps]
  out_ref, acc_ref, m_ref, l_ref = refs[2 * pps:]
  b = pl.program_id(0)
  j = pl.program_id(1)
  length = length_ref[b]

  @pl.when(j == 0)
  def _init():
    _init_heads(acc_ref, m_ref, l_ref)

  @pl.when(j * pps * blk < length)
  def _group():
    _accumulate_group(
        q_ref[0], jnp.concatenate([r[0, 0] for r in k_refs], axis=1),
        jnp.concatenate([r[0, 0] for r in v_refs], axis=1),
        j * pps * blk, length, scale, acc_ref, m_ref, l_ref)

  @pl.when(j == pl.num_programs(1) - 1)
  def _done():
    _finalize(out_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_decode_kernel(
    q: jax.Array,          # (B, H, g, d)
    k_pool: jax.Array,     # (P+1, L, H, blk, d)
    v_pool: jax.Array,     # (P+1, L, H, blk, d)
    tables: jax.Array,     # (B, nb) int32 — logical block j -> pool block
    layer: jax.Array,      # (1,) int32
    length: jax.Array,     # (B,) int32 — valid tokens per row
    scale: float,
    interpret: bool,
    new: Optional[Tuple[jax.Array, jax.Array]] = None,
):
  """Block-table-native flash decode over pooled K/V: (B, H, g, d) f32.

  With ``new`` = (k_new, v_new), each (B, H, d), row ``length - 1`` of each
  table (its page clamped to the table) is first set to them, and
  (out, k_pool, v_pool) is returned.  Hand-DMA head dims write the row
  inside the kernel, pools aliased in place; narrower ones scatter it in
  XLA before the call.
  """
  n_rows, h, g, d = q.shape
  blk = k_pool.shape[3]
  if k_pool.shape[2] != h:
    raise ValueError(f"q has {h} kv heads, the pool {k_pool.shape[2]}")
  nb = tables.shape[1]
  pps = pages_per_step(nb, h, g, blk, d, k_pool.dtype.itemsize)
  grid = (n_rows, pl.cdiv(nb, pps))
  by_hand = d % LANES == 0
  append = new is not None and by_hand
  with timing.span("kernel.paged_flash_decode", pps=pps, grid=grid,
                   blk=blk, dma="manual" if by_hand else "pipelined",
                   append="none" if new is None else
                   "kernel" if append else "scatter"):
    pass
  if new is not None:
    k_new, v_new = (x.astype(k_pool.dtype) for x in new)
  if new is not None and not append:
    pos = length - 1
    pids = tables[jnp.arange(n_rows), jnp.minimum(pos // blk, nb - 1)]
    k_pool = k_pool.at[pids, layer[0], :, pos % blk].set(k_new)
    v_pool = v_pool.at[pids, layer[0], :, pos % blk].set(v_new)
  row = pl.BlockSpec((1, h, g, d), lambda b, j, tbl, lyr, L: (b, 0, 0, 0))
  out_shape = jax.ShapeDtypeStruct((n_rows, h, g, d), jnp.float32)
  heads = [pltpu.VMEM((h, g, d), jnp.float32),
           pltpu.VMEM((h, g, 1), jnp.float32),
           pltpu.VMEM((h, g, 1), jnp.float32)]
  aliases = {}
  if by_hand:
    kernel = functools.partial(_paged_flash_decode_kernel, scale=scale,
                               blk=blk, pps=pps, nb=nb, append=append)
    pools = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    scratch = [pltpu.VMEM((2, h, pps * blk, d), k_pool.dtype),
               pltpu.VMEM((2, h, pps * blk, d), v_pool.dtype),
               pltpu.SemaphoreType.DMA((2, 3)),
               pltpu.SMEM((2,), jnp.int32)] + heads
    operands = (k_pool, v_pool)
    out_specs = row
    if append:
      # k_new, v_new ride as (1, H, 1, d) blocks: a row broadcast over a
      # page's rows; the pools, operands 6 and 7 counting the three
      # prefetched scalars, come back as outputs 1 and 2
      one = pl.BlockSpec((1, h, 1, d), lambda b, j, tbl, lyr, L: (b, 0, 0, 0))
      pools = [one, one] + pools
      operands = (k_new[:, :, None], v_new[:, :, None]) + operands
      out_specs = [row] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
      out_shape = (out_shape, jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype))
      aliases = {6: 1, 7: 2}
  else:
    # one pipelined input per page of the group; an entry past the row's
    # length maps to its last live page, never to the trash block
    def page(i):
      def index(b, j, tbl, lyr, L):
        last = jnp.maximum(pl.cdiv(L[b], blk), 1) - 1
        return tbl[b * nb + jnp.minimum(j * pps + i, last)], lyr[0], 0, 0, 0
      return pl.BlockSpec((1, 1, h, blk, d), index)
    kernel = functools.partial(
        _paged_flash_decode_pipelined_kernel, scale=scale, blk=blk, pps=pps)
    pools = [page(i) for i in range(pps)] * 2
    scratch = heads
    operands = (k_pool,) * pps + (v_pool,) * pps
    out_specs = row
  result = pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=3,
          grid=grid,
          in_specs=[row] + pools,
          out_specs=out_specs,
          scratch_shapes=scratch,
      ),
      out_shape=out_shape,
      input_output_aliases=aliases,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary", "arbitrary")),
      interpret=interpret,
      name="paged_flash_decode",
  )(tables.reshape(-1), layer, length, q, *operands)
  if new is None or append:
    return result
  return result, k_pool, v_pool


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
