"""Decode-kernel dispatch: oracle parity for the Pallas decode kernels
(interpret mode) and serve-level greedy token identity across dispatches.

Three layers of guarantee, matching the PR 5 numerics contract:

  1. kernel vs pure-JAX oracle — the dense PQ body kernel against
     `pq_decode_attention`'s math, the paged PQ kernel against the dense one
     on a gathered view, and paged flash decode against
     `exact_decode_attention`, across randomized (g, m, K, dsub, block,
     ragged lengths) — fp32-accumulation tolerance;
  2. policy-level — `append_and_attend` under xla vs pallas-interpret
     dispatch agrees on identical state;
  3. serve-level — greedy tokens bit-identical across
     `--decode-kernel {xla, pallas-interpret}` for
     `{paged, tiered} x {exact, pq}` (the acceptance matrix), including a
     forced spill/fetch on the tiered runs.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
  from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover — CI image has no hypothesis
  from hypothesis_compat import given, settings, strategies as st

from repro.configs import get_arch
from repro.core import cache_api, cache_registry, decode_dispatch
from repro.core import kv_cache as kvc
from repro.core import pq as pqlib
from repro.core import pq_attention as pqa
from repro.kernels import ops, ref
from repro.kernels import paged_flash_decode as pfd
from repro.launch.engine import ServeEngine


# ---------------------------------------------------------------------------
# dispatch registry
# ---------------------------------------------------------------------------

def test_registry_names_and_resolution():
  assert decode_dispatch.names() == ("auto", "pallas", "pallas-interpret",
                                     "xla")
  assert decode_dispatch.resolve("xla").use_pallas is False
  d = decode_dispatch.resolve("pallas-interpret")
  assert d.use_pallas and d.interpret and d.key == "pallas-interpret"
  with pytest.raises(ValueError):
    decode_dispatch.validate("mosaic")
  auto = decode_dispatch.resolve("auto")
  if jax.default_backend() != "tpu":
    assert auto.use_pallas is False      # auto degrades to xla off-TPU
    with pytest.raises(ValueError):      # compiled Mosaic needs a TPU
      decode_dispatch.resolve("pallas")


def test_cache_spec_validates_decode_kernel():
  with pytest.raises(ValueError):
    cache_api.CacheSpec(capacity=32, head_dim=16, window=16,
                        decode_kernel="nope")


# ---------------------------------------------------------------------------
# kernel vs oracle (hypothesis sweep)
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(
    g=st.integers(1, 4),
    m=st.sampled_from([2, 4, 8]),
    k_cent=st.sampled_from([8, 16, 64]),
    dsub=st.sampled_from([2, 4, 8]),
    blk=st.sampled_from([8, 16, 32]),
    nb=st.integers(2, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_paged_pq_kernel_matches_dense_kernel_and_oracle(
    g, m, k_cent, dsub, blk, nb, seed):
  """Paged PQ kernel == dense PQ kernel == pure-JAX oracle on the gathered
  view, under random block tables, trash entries, and ragged lengths."""
  rng = np.random.default_rng(seed)
  b, h, layers = 2, 2, 2
  d = m * dsub
  n = blk * nb
  pool_blocks = b * nb + 3
  trash = pool_blocks
  layer = int(rng.integers(0, layers))

  q = jnp.asarray(rng.normal(size=(b, h, g, d)), jnp.float32)
  kcb = jnp.asarray(rng.normal(size=(b, h, m, k_cent, dsub)), jnp.float32)
  vcb = jnp.asarray(rng.normal(size=(b, h, m, k_cent, dsub)), jnp.float32)
  idt = np.uint8 if k_cent <= 256 else np.int16
  kip = jnp.asarray(rng.integers(0, k_cent,
                                 size=(pool_blocks + 1, layers, h, blk, m)),
                    idt)
  vip = jnp.asarray(rng.integers(0, k_cent,
                                 size=(pool_blocks + 1, layers, h, blk, m)),
                    idt)
  tables = rng.permutation(pool_blocks)[:b * nb].reshape(b, nb).astype(
      np.int32)
  lengths = rng.integers(0, n + 1, size=(b,)).astype(np.int32)
  for i in range(b):   # entries past the extent point at trash (unallocated)
    for j in range(-(-int(lengths[i]) // blk), nb):
      tables[i, j] = trash
  scale = 1 / np.sqrt(d)

  p_out, p_m, p_l = ops.pq_decode_attention_paged(
      q, kcb, vcb, kip, vip, jnp.asarray(tables), jnp.asarray(layer),
      jnp.asarray(lengths), scale)

  # dense view gathered from the pool (trash rows land past `lengths`)
  kix = np.stack([np.concatenate(
      [np.asarray(kip[tables[i, j], layer], np.int32) for j in range(nb)],
      axis=1) for i in range(b)])                       # (B, H, N, m)
  vix = np.stack([np.concatenate(
      [np.asarray(vip[tables[i, j], layer], np.int32) for j in range(nb)],
      axis=1) for i in range(b)])
  d_out, d_m, d_l = ops.pq_decode_attention(
      q, kcb, vcb, jnp.asarray(kix), jnp.asarray(vix),
      jnp.asarray(np.broadcast_to(lengths[:, None], (b, h)).copy()), scale,
      blk=blk)
  np.testing.assert_allclose(np.asarray(p_out), np.asarray(d_out),
                             rtol=1e-4, atol=1e-4)
  np.testing.assert_allclose(np.asarray(p_l), np.asarray(d_l),
                             rtol=1e-4, atol=1e-4)

  r_out, r_stats = ref.pq_decode_attention_ref(
      np.asarray(q).reshape(b * h, g, d),
      np.asarray(kcb).reshape(b * h, m, k_cent, dsub),
      np.asarray(vcb).reshape(b * h, m, k_cent, dsub),
      jnp.asarray(kix.reshape(b * h, n, m)),
      jnp.asarray(vix.reshape(b * h, n, m)),
      jnp.asarray(np.repeat(lengths, h)), scale)
  np.testing.assert_allclose(np.asarray(p_out).reshape(b * h, g, d),
                             np.asarray(r_out), rtol=1e-4, atol=1e-4)


def _check_paged_flash_decode(g, d, blk, nb, h, lengths, seed):
  """Paged flash decode == exact_decode_attention on the gathered view.

  Every pool row past a row's length is NaN: the trash block that
  unallocated table entries point at, and the tail of the last live page.
  None of it may reach the sum (0 * NaN is NaN).
  """
  rng = np.random.default_rng(seed)
  lengths = np.asarray(lengths, np.int32)
  b, layers = len(lengths), 2
  n = blk * nb
  pool_blocks = b * nb + 2
  trash = pool_blocks
  layer = int(rng.integers(0, layers))
  q = jnp.asarray(rng.normal(size=(b, h, g, d)), jnp.float32)
  k_pool = rng.normal(size=(pool_blocks + 1, layers, h, blk, d))
  v_pool = rng.normal(size=(pool_blocks + 1, layers, h, blk, d))
  tables = rng.permutation(pool_blocks)[:b * nb].reshape(b, nb).astype(
      np.int32)
  for i in range(b):
    live = -(-int(lengths[i]) // blk)
    tables[i, live:] = trash
    if lengths[i] % blk:
      for pool in (k_pool, v_pool):
        pool[tables[i, live - 1], :, :, lengths[i] % blk:] = np.nan
  k_pool[trash] = v_pool[trash] = np.nan
  scale = 1 / np.sqrt(d)
  out = np.asarray(ops.paged_flash_decode(
      q, jnp.asarray(k_pool, jnp.float32), jnp.asarray(v_pool, jnp.float32),
      jnp.asarray(tables), jnp.asarray(layer), jnp.asarray(lengths), scale))
  assert np.isfinite(out).all()
  for i in range(b):
    mask = np.arange(n) < lengths[i]
    for hh in range(h):
      kd, vd = (np.where(mask[:, None], np.concatenate(
          [pool[tables[i, j], layer, hh] for j in range(nb)]), 0.0)
                for pool in (k_pool, v_pool))
      want = pqa.exact_decode_attention(
          q[i, hh], jnp.asarray(kd, jnp.float32),
          jnp.asarray(vd, jnp.float32), jnp.asarray(mask), scale)
      np.testing.assert_allclose(out[i, hh], np.asarray(want),
                                 rtol=1e-4, atol=1e-4, err_msg=f"bh {i},{hh}")


@settings(max_examples=8, deadline=None)
@given(
    g=st.integers(1, 4),
    d=st.sampled_from([8, 16, 32, 128]),
    blk=st.sampled_from([8, 16]),
    nb=st.integers(2, 40),
    seed=st.integers(0, 2**31 - 1),
)
def test_paged_flash_decode_matches_exact_oracle(g, d, blk, nb, seed):
  """Random widths, table sizes (ragged page groups past 16 or 32 pages)
  and lengths; d=128 fetches pages by hand, narrower heads pipelined."""
  lengths = np.random.default_rng(seed).integers(1, blk * nb + 1, size=2)
  _check_paged_flash_decode(g, d, blk, nb, 2, lengths, seed)


# (g, d, blk, nb, kv heads, lengths); a group is 16 pages at blk=16
@pytest.mark.parametrize("g,d,blk,nb,h,lengths", [
    pytest.param(3, 128, 16, 21, 2, [300, 17, 336], id="nb-not-multiple"),
    pytest.param(3, 128, 16, 21, 2, [1, 1], id="length-1"),
    pytest.param(3, 128, 16, 21, 2, [16, 48], id="length-blk"),
    pytest.param(3, 128, 16, 21, 2, [256, 257], id="length-one-group"),
    pytest.param(3, 128, 16, 21, 2, [336, 336], id="length-full-table"),
    pytest.param(7, 128, 16, 18, 8, [1, 256, 288, 100], id="yi34b-heads"),
    pytest.param(8, 64, 16, 20, 4, [1, 16, 256, 320], id="pipelined-d64"),
])
def test_paged_flash_decode_matches_exact_oracle_at(g, d, blk, nb, h,
                                                     lengths):
  _check_paged_flash_decode(g, d, blk, nb, h, lengths, seed=len(lengths))


def _scatter_then_attend(k_pool, v_pool, layer, tables, q, k_new, v_new,
                         lengths, scale):
  """The exact paged step as an XLA row scatter, then the read kernel."""
  b, hq, d = q.shape
  h, blk = k_pool.shape[2], k_pool.shape[3]
  pids = tables[jnp.arange(b), lengths // blk]
  k_pool = k_pool.at[pids, layer, :, lengths % blk].set(k_new)
  v_pool = v_pool.at[pids, layer, :, lengths % blk].set(v_new)
  out = ops.paged_flash_decode(q.reshape(b, h, hq // h, d), k_pool, v_pool,
                               tables, layer, lengths + 1, scale)
  return out.reshape(b, hq, d), k_pool, v_pool


# yi-34b-exact's heads (h=8, g=7, d=128) over 18 pages of 16: a group is
# pps = 16 pages, so 255 / 256 straddle the group boundary
@pytest.mark.parametrize("lengths", [
    pytest.param([32, 100], id="page-start"),
    pytest.param([37, 5], id="mid-page"),
    pytest.param([15, 47], id="page-last-row"),
    pytest.param([255, 256], id="group-boundary"),
    pytest.param([18 * 16 - 1, 1], id="table-last-row"),
    pytest.param([0, 130], id="trash-row"),
])
def test_exact_paged_step_writes_row_like_scatter(lengths):
  """The exact paged step, whose kernel writes the new row by whole-page
  write-back into pools aliased in place, against an XLA scatter then
  attention: output and both pools (every block but the trash block, NaN
  rows past the lengths included) bit-identical, over a 2-layer scan in
  which each layer writes its own plane."""
  h, g, d, blk, nb, layers = 8, 7, 128, 16, 18, 2
  assert pfd.pages_per_step(nb, h, g, blk, d, 2) == 16
  rng = np.random.default_rng(sum(lengths))
  b = len(lengths)
  lengths = np.asarray(lengths, np.int32)
  trash = b * nb
  tables = rng.permutation(trash).reshape(b, nb).astype(np.int32)
  k_pool = rng.normal(size=(trash + 1, layers, h, blk, d))
  v_pool = rng.normal(size=(trash + 1, layers, h, blk, d))
  for i, n in enumerate(lengths):
    # the new row's page is allocated; a row of length 0 has none yet
    tables[i, n // blk + 1 if n else 0:] = trash
    for pool in (k_pool, v_pool):
      pool[tables[i, n // blk], :, :, n % blk:] = np.nan
  bf16 = jnp.bfloat16
  k_pool, v_pool = jnp.asarray(k_pool, bf16), jnp.asarray(v_pool, bf16)
  q = jnp.asarray(rng.normal(size=(layers, b, h * g, d)), bf16)
  k_new = jnp.asarray(rng.normal(size=(layers, b, h, d)), bf16)
  v_new = jnp.asarray(rng.normal(size=(layers, b, h, d)), bf16)
  tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)

  def scan(step):
    def layer(carry, x):
      i, kp, vp = carry
      out, kp, vp = step(kp, vp, i, tables, *x, lengths, d ** -0.5)
      return (i + 1, kp, vp), out
    (_, kp, vp), out = jax.lax.scan(
        layer, (jnp.int32(0), k_pool, v_pool), (q, k_new, v_new))
    return out, kp, vp

  want = scan(_scatter_then_attend)
  got = scan(functools.partial(kvc.exact_cache_paged_step, interpret=True))
  assert np.isfinite(np.asarray(got[0], np.float32)).all()
  np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
  for new_pool, old_pool in zip(got[1:], want[1:]):
    np.testing.assert_array_equal(np.asarray(new_pool[:trash], np.float32),
                                  np.asarray(old_pool[:trash], np.float32))


def test_dense_flash_decode_matches_exact_oracle():
  rng = np.random.default_rng(11)
  b, h, g, n, d = 2, 2, 3, 48, 16
  q = jnp.asarray(rng.normal(size=(b, h, g, d)), jnp.float32)
  k = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
  v = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
  lengths = jnp.asarray([48, 29], jnp.int32)
  out = ops.flash_decode(q, k, v, lengths, 0.25, blk=16)
  for i in range(b):
    for hh in range(h):
      mask = np.arange(n) < int(lengths[i])
      want = pqa.exact_decode_attention(q[i, hh], k[i, hh], v[i, hh],
                                        jnp.asarray(mask), 0.25)
      np.testing.assert_allclose(np.asarray(out[i, hh]), np.asarray(want),
                                 rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# policy-level parity (dense storage)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ("exact", "pq"))
def test_policy_append_and_attend_kernel_parity(name):
  rng = np.random.default_rng(5)
  b, h, hq, n, cap, d = 2, 2, 4, 24, 48, 16
  k = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
  v = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
  q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
  kn = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
  vn = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
  w = jnp.ones((b, h, n), jnp.float32)
  pq_geo = kvc.PQCacheConfig(sink=4, recent=8, body_capacity=64, n_windows=1,
                             pq=pqlib.PQConfig(m=4, k=16))
  spec_x = cache_api.CacheSpec(capacity=cap, head_dim=d, sink=4, recent=8,
                               window=16, decode_kernel="xla",
                               pq=pq_geo if name == "pq" else None)
  spec_p = dataclasses.replace(spec_x, decode_kernel="pallas-interpret")
  px = cache_registry.make(name, spec_x)
  pp = cache_registry.make(name, spec_p)
  assert not px.use_kernel and pp.use_kernel
  assert pp.block_native and not px.block_native
  lengths = jnp.asarray([n, n - 5], jnp.int32)
  stt = px.prefill(k, v, w if px.needs_weights else None, lengths)
  ox, sx = px.append_and_attend(stt, q, kn, vn, lengths)
  op, sp = pp.append_and_attend(stt, q, kn, vn, lengths)
  np.testing.assert_allclose(np.asarray(ox), np.asarray(op),
                             rtol=1e-4, atol=1e-4)
  for a, bb in zip(jax.tree_util.tree_leaves(sx),
                   jax.tree_util.tree_leaves(sp)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(bb, np.float32),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# serve-level greedy token identity: {xla, pallas-interpret} x
# {paged, tiered} x {exact, pq}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ("paged", "tiered"))
@pytest.mark.parametrize("policy", ("exact", "pq"))
def test_serve_tokens_identical_across_decode_kernels(layout, policy):
  base = dataclasses.replace(
      get_arch("tinyllama-1.1b", reduced=True), cache_policy=policy,
      cache_layout=layout, scheduler=layout)
  if policy == "exact":
    kwargs = dict(context_len=64, max_batch=2, prompt_capacity=32)
    trace = [(list(range(1, 21)), 14), (list(range(3, 25)), 14),
             ([7] * 9, 6)]
    if layout == "tiered":
      # pool sized below the trace's KV growth (test_tiers recipe): the run
      # must spill and fetch, proving the block-native program coexists with
      # swap preemption
      kwargs.update(num_blocks=5, host_blocks=16)
  else:
    # pq pages only body tokens (length beyond sink+recent): longer prompts
    # so the code rows actually occupy — and overflow — the device pool
    kwargs = dict(context_len=96, max_batch=2, prompt_capacity=64)
    trace = [(list(range(2, 60)), 24), (list(range(4, 49)), 24)]
    if layout == "tiered":
      kwargs.update(num_blocks=7, host_blocks=32)
  outs, params, engines = {}, None, {}
  for kern in ("xla", "pallas-interpret"):
    cfg = dataclasses.replace(base, decode_kernel=kern)
    eng = ServeEngine(cfg, params=params, **kwargs)
    params = eng.params
    handles = [eng.submit(p, max_new_tokens=mx) for p, mx in trace]
    eng.run_to_completion()
    outs[kern] = [h.tokens for h in handles]
    engines[kern] = eng
  assert outs["xla"] == outs["pallas-interpret"], (layout, policy)
  native = engines["pallas-interpret"].layout
  assert native.block_native
  assert native.decode_traffic["dense_materialized_bytes_per_step"] == 0
  assert native.decode_traffic["block_read_bytes_per_step"] > 0
  assert not engines["xla"].layout.block_native
  if layout == "tiered":
    for eng in engines.values():
      assert eng.stats.spills >= 1, "trace never hit pool pressure"

# CLI flag threading (--decode-kernel -> ModelConfig -> layout) is covered
# alongside the other serve flags in tests/test_serve_cli.py.
