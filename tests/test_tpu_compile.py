"""Ahead-of-time compiles of the serve-path decode kernels for a TPU v5e.

Nothing runs: each kernel is lowered and compiled by the installed TPU
compiler for a described (not attached) v5e, at tinyllama-1.1b's full
widths — g=8 query heads per kv head, head dim 64, PQ m=32 / K=512
(dsub=2), 16-token pool blocks over 22 layers.  Interpret mode cannot show
what these do: Mosaic refuses shape casts, gathers and loads it does not
lower, DMA slices narrower than a lane tile, and fast-memory budgets it
cannot meet.  The exact paged kernel also compiles at yi-34b-exact's
shapes (head dim 128, g=7), where it fetches pool pages by hand, and the
exact policy's paged step compiles in a 4-layer scan there without a
whole-pool copy or scatter: the kernel writes the new row itself.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, so the worker that runs this file
loads it and the others never try.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import kv_cache as kvc
from repro.kernels import paged_flash_decode as pfd
from repro.kernels import pq_decode as pqd

BH, G, D, M, K, DSUB = 16, 8, 64, 32, 512, 2   # 4 requests x 4 kv heads
LAYERS, HEADS, BLOCK = 22, 4, 16
POOL = 4 * 65 + 1                                # 4 x 1040-token tables + trash
NB = 65
SCALE = D ** -0.5


@pytest.fixture(scope="module")
def one_chip():
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  # a described device's programs can be written to the persistent cache
  # but never read back; keep them out of it
  enabled = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield SingleDeviceSharding(topo.devices[0])
  jax.config.update("jax_enable_compilation_cache", enabled)


def _pq_dense(arg):
  n = 512                                        # ServeRun body, blk 512
  return pqd.pq_decode_attention_kernel.lower(
      arg((BH, G, D), jnp.bfloat16), arg((BH, DSUB, M, K), jnp.float32),
      arg((BH, DSUB, M, K), jnp.float32), arg((BH, n, M), jnp.int32),
      arg((BH, n, M), jnp.int32), arg((BH,), jnp.int32),
      scale=SCALE, blk=n, interpret=False)


def _pq_paged(arg):
  return pqd.pq_decode_attention_paged_kernel.lower(
      arg((BH, G, D), jnp.bfloat16), arg((BH, DSUB, M, K), jnp.float32),
      arg((BH, DSUB, M, K), jnp.float32),
      arg((POOL, LAYERS, HEADS, BLOCK, M), jnp.int16),
      arg((POOL, LAYERS, HEADS, BLOCK, M), jnp.int16),
      arg((BH, NB), jnp.int32), arg((1,), jnp.int32), arg((BH,), jnp.int32),
      scale=SCALE, interpret=False)


def _flash(arg):
  n = 1024
  return pfd.flash_decode_kernel.lower(
      arg((BH, G, D), jnp.bfloat16), arg((BH, n, D), jnp.bfloat16),
      arg((BH, n, D), jnp.bfloat16), arg((BH,), jnp.int32),
      scale=SCALE, blk=512, interpret=False)


def _paged_flash_at(rows, heads, g, d, pool, layers, nb):
  def lower(arg):
    return pfd.paged_flash_decode_kernel.lower(
        arg((rows, heads, g, d), jnp.bfloat16),
        arg((pool, layers, heads, BLOCK, d), jnp.bfloat16),
        arg((pool, layers, heads, BLOCK, d), jnp.bfloat16),
        arg((rows, nb), jnp.int32), arg((1,), jnp.int32),
        arg((rows,), jnp.int32), scale=d ** -0.5, interpret=False)
  return lower


_paged_flash = _paged_flash_at(BH // HEADS, HEADS, G, D, POOL, LAYERS, NB)
# yi-34b-exact's cell: 16 rows of a 4096-token table (256 pages), 8 kv
# heads of 128 at g=7, 4 layers, 4096 pages and the trash page
_paged_flash_yi34b = _paged_flash_at(16, 8, 7, 128, 4097, 4, 256)


def _packed(bits):
  def lower(arg):
    codes = arg((POOL, LAYERS, HEADS, BLOCK, D * bits // 8), jnp.uint8)
    header = arg((POOL, LAYERS, HEADS, BLOCK, D // 32), jnp.float16)
    return pfd.packed_paged_flash_decode_kernel.lower(
        arg((BH, G, D), jnp.bfloat16), codes, header, header, codes, header,
        header, arg((BH, NB), jnp.int32), arg((1,), jnp.int32),
        arg((BH,), jnp.int32), scale=SCALE, bits=bits, interpret=False)
  return lower


@pytest.mark.parametrize("lower", [
    pytest.param(_pq_dense, id="pq_decode_attention_kernel"),
    pytest.param(_pq_paged, id="pq_decode_attention_paged_kernel"),
    pytest.param(_flash, id="flash_decode_kernel"),
    pytest.param(_paged_flash, id="paged_flash_decode_kernel"),
    pytest.param(_paged_flash_yi34b, id="paged_flash_decode_kernel-yi34b"),
    pytest.param(_packed(4), id="packed_paged_flash_decode_kernel-q4"),
    pytest.param(_packed(8), id="packed_paged_flash_decode_kernel-q8"),
])
def test_decode_kernel_compiles_for_v5e(one_chip, lower):
  def arg(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
  compiled = lower(arg).compile()
  assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("d", [
    pytest.param(128, id="yi34b-kernel-writes"),
    pytest.param(64, id="d64-scatter"),
])
def test_exact_paged_step_scan_compiles_for_v5e(one_chip, d):
  """yi-34b-exact's decode: 4 layers scanned over one donated pool pair,
  16 rows of 256-entry tables, 8 kv heads at g=7.  At head dim 128 the
  kernel writes each new row by whole-page DMA into pools aliased in
  place, so no op of the program makes a pool-sized copy, scatter or
  fusion (a bf16 pool's pages share 32-bit words between row pairs: an
  XLA row scatter re-lays the whole pool out, 12 copies a step).  At 64
  the row is scattered in XLA; that path need only compile."""
  pool, layers, heads, g, rows, nb = 4097, 4, 8, 7, 16, 256

  def arg(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

  def step(k_pool, v_pool, tables, q, k_new, v_new, lengths):
    def layer(carry, x):
      i, kp, vp = carry
      out, kp, vp = kvc.exact_cache_paged_step(
          kp, vp, i, tables, *x, lengths, d ** -0.5, interpret=False)
      return (i + 1, kp, vp), out
    (_, k_pool, v_pool), out = jax.lax.scan(
        layer, (jnp.int32(0), k_pool, v_pool), (q, k_new, v_new))
    return out, k_pool, v_pool

  pools = arg((pool, layers, heads, BLOCK, d), jnp.bfloat16)
  compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
      pools, pools, arg((rows, nb), jnp.int32),
      arg((layers, rows, heads * g, d), jnp.bfloat16),
      arg((layers, rows, heads, d), jnp.bfloat16),
      arg((layers, rows, heads, d), jnp.bfloat16),
      arg((rows,), jnp.int32)).compile()
  hlo = compiled.as_text()
  assert 'custom_call_target="tpu_custom_call"' in hlo
  if d % pfd.LANES == 0:
    shape = re.escape(f"bf16[{pool},{layers},{heads},{BLOCK},{d}]")
    writes = re.findall(
        rf"%\S+ = {shape}\{{[^}}]*\}} (?:copy|scatter|fusion)\(", hlo)
    assert not writes, writes
