"""Sub-byte packed KV (kernels/packing.py + the packed exact policy):
bit-unpack roundtrip invariants, quantization error bounds (incl. the
worst-case dynamic-range and constant-group degenerate paths), the Pallas
unpack primitive vs the jnp reference, paged-kernel vs XLA decode parity,
and the resident-q4 footprint/error acceptance numbers."""
import dataclasses

try:
  from hypothesis import given, settings, strategies as st
except ImportError:  # container lacks hypothesis: seeded fallback shim
  from hypothesis_compat import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import cache_api, cache_registry, tiers
from repro.core import kv_cache as kvc
from repro.core import pq_attention
from repro.kernels import packing
from repro.launch.engine import ServeEngine

#: f16 relative rounding slack: scale/min are stored f16, so reconstruction
#: error exceeds the ideal half-step by at most ~2^-11 of the group magnitude.
F16_EPS = 2 ** -11
#: f16 absolute rounding slack below the normal range (|v| < 2^-14): a
#: subnormal half rounds to a multiple of 2^-24, off by at most 2^-25.
F16_SUB_HALF_ULP = 2 ** -25


def _spec(**kw):
  kw.setdefault("capacity", 64)
  kw.setdefault("head_dim", 16)
  kw.setdefault("window", 64)
  return cache_api.CacheSpec(**kw)


# ---------------------------------------------------------------------------
# Bit pack/unpack: exact inverses
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000), d=st.sampled_from([2, 8, 16, 64, 128]),
       n=st.integers(1, 17))
def test_pack_unpack_u4_roundtrip_exact(seed, d, n):
  rng = np.random.default_rng(seed)
  q = jnp.asarray(rng.integers(0, 16, size=(n, d)), jnp.uint8)
  p = packing.pack_u4(q)
  assert p.shape == (n, d // 2) and p.dtype == jnp.uint8
  back = packing.unpack_u4(p)
  assert back.dtype == jnp.int32
  np.testing.assert_array_equal(np.asarray(back), np.asarray(q, np.int32))


def test_pack_u4_is_split_half_not_interleaved():
  # byte j must carry code j (low nibble) and code j + d/2 (high nibble):
  # the layout that makes unpack a single concat, no gather
  q = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.uint8)
  p = np.asarray(packing.pack_u4(q))
  np.testing.assert_array_equal(
      p[0], [1 | (5 << 4), 2 | (6 << 4), 3 | (7 << 4), 4 | (8 << 4)])


def test_unpack_u4_kernel_matches_reference(rng):
  p = jnp.asarray(rng.integers(0, 256, size=(24, 8)), jnp.uint8)
  got = packing.unpack_u4_kernel(p, interpret=True)
  assert got.shape == (24, 16) and got.dtype == jnp.int32
  np.testing.assert_array_equal(np.asarray(got),
                                np.asarray(packing.unpack_u4(p)))


# ---------------------------------------------------------------------------
# Quantize/dequantize: error bounds
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), bits=st.sampled_from([4, 8]),
       d=st.sampled_from([8, 16, 32, 64]),
       mag=st.sampled_from([1e-3, 1.0, 3.0, 1e3]))
def test_quantize_roundtrip_error_half_step(seed, bits, d, mag):
  """|x - dequant(quant(x))| <= scale/2 per group (+ f16 header rounding),
  across magnitudes from sub-f16-step to 1e3 and with negative values."""
  rng = np.random.default_rng(seed)
  group = packing.group_size(d)
  x = jnp.asarray(rng.normal(scale=mag, size=(3, d)), jnp.float32)
  q, scale, mn = packing.quantize_rows(x, bits=bits, group=group)
  assert q.dtype == jnp.uint8 and scale.dtype == jnp.float16
  assert int(q.max()) <= (1 << bits) - 1
  back = packing.dequantize_rows(q, scale, mn, group=group)
  err = np.abs(np.asarray(back) - np.asarray(x)).reshape(3, d // group, group)
  absmax = np.abs(np.asarray(x)).reshape(3, d // group, group).max(-1)
  step = np.asarray(scale, np.float32)
  # half a step, plus the f16 rounding of scale (amplified by up to qmax
  # codes) and of min; each rounding is relative for normal halves and
  # absolute for subnormal ones (small-magnitude q8 scales land there)
  qmax = (1 << bits) - 1
  tol = (0.5 * step + F16_EPS * (step * qmax + absmax)
         + F16_SUB_HALF_ULP * (qmax + 1) + 1e-12)
  assert (err.max(-1) <= tol).all(), (err.max(), tol.min())


def test_quantize_constant_group_degrades_to_min(rng):
  # zero range -> f16 scale 0 -> codes 0, dequant returns the f16 minimum
  x = jnp.full((2, 16), 0.7183, jnp.float32)
  q, scale, mn = packing.quantize_rows(x, bits=4, group=16)
  assert int(np.asarray(q).max()) == 0
  assert float(np.abs(np.asarray(scale, np.float32)).max()) == 0.0
  back = np.asarray(packing.dequantize_rows(q, scale, mn, group=16))
  assert np.abs(back - 0.7183).max() <= 0.7183 * F16_EPS


def test_quantize_worst_case_dynamic_range_stays_finite():
  """One huge outlier per group (the case that breaks symmetric quant):
  params stay finite f16, small values collapse toward min but the big one
  survives within half a (now huge) step."""
  x = np.full((1, 32), 1e-4, np.float32)
  x[0, 7] = 6.0e4          # near f16 max; range/15 and min still fit f16
  x[0, 19] = -6.0e4
  q, scale, mn = packing.quantize_rows(jnp.asarray(x), bits=4, group=32)
  assert np.isfinite(np.asarray(scale, np.float32)).all()
  assert np.isfinite(np.asarray(mn, np.float32)).all()
  back = np.asarray(packing.dequantize_rows(q, scale, mn, group=32))
  assert np.isfinite(back).all()
  step = float(np.asarray(scale, np.float32)[0, 0])
  assert np.abs(back - x).max() <= 0.5 * step + F16_EPS * (15 * step + 6e4)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), bits=st.sampled_from([4, 8]))
def test_dequant_page_equals_unpack_then_dequant(seed, bits):
  """The one shared formula: dequant_page == dequantize_rows over the
  unpacked codes, bit for bit (this identity is why kernel and XLA paths
  reconstruct identical K/V)."""
  rng = np.random.default_rng(seed)
  x = jnp.asarray(rng.normal(size=(2, 3, 32)), jnp.float32)
  pack, scale, mn = packing.pack_rows(x, bits=bits, group=32)
  assert pack.shape[-1] == packing.packed_width(32, bits)
  via_page = packing.dequant_page(pack, scale, mn, bits=bits, group=32)
  codes = packing.unpack_u4(pack) if bits == 4 else pack
  via_rows = packing.dequantize_rows(codes, scale, mn, group=32)
  np.testing.assert_array_equal(np.asarray(via_page), np.asarray(via_rows))


# ---------------------------------------------------------------------------
# Packed exact cache: paged kernel vs dense XLA parity, error bound
# ---------------------------------------------------------------------------

def test_packed_paged_kernel_matches_dense_xla_attend(rng):
  """Same packed rows through the block-native Pallas(-interpret) kernel and
  the dense masked-XLA attend: outputs agree to float tolerance (tokens are
  therefore identical downstream)."""
  b, h, d, block, bits = 2, 2, 16, 8, 4
  n_blocks, capacity = 3, 24
  lengths = jnp.asarray([13, 7], jnp.int32)
  k = jnp.asarray(rng.normal(size=(b, h, capacity, d)), jnp.float32)
  v = jnp.asarray(rng.normal(size=(b, h, capacity, d)), jnp.float32)
  q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
  k_new = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
  v_new = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
  scale = d ** -0.5

  cache = kvc.packed_exact_cache_prefill(k, v, capacity, bits)
  want, _ = kvc.packed_exact_cache_append_and_attend(
      cache, q, k_new, v_new, lengths, scale, bits, use_kernel=False,
      interpret=False)

  # scatter the same dense store into pool blocks (pool id 0 = null block)
  tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
  pools = []
  for leaf in cache:
    width = leaf.shape[-1]
    pool = jnp.zeros((b * n_blocks + 1, 1, h, block, width), leaf.dtype)
    rows = leaf.reshape(b, h, n_blocks, block, width)
    for i in range(b):
      for j in range(n_blocks):
        pool = pool.at[int(tables[i, j]), 0].set(rows[i, :, j])
    pools.append(pool)
  got, _ = kvc.packed_exact_cache_paged_step(
      pools, jnp.asarray(0, jnp.int32), tables, q, k_new, v_new,
      lengths, scale, bits, interpret=True)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             atol=1e-5, rtol=1e-5)

  # max-abs-error vs the *unquantized* fp32 oracle on the same paged trace:
  # quantization noise is bounded and shrinks 16x from q4 to q8 (half-step
  # scales with 1/(2^bits - 1)); measured ~0.084 / ~0.005 on this seed
  kf, vf = k, v
  for i in range(b):
    kf = kf.at[i, :, int(lengths[i])].set(k_new[i])
    vf = vf.at[i, :, int(lengths[i])].set(v_new[i])

  def oracle(kk, vv, qq, ln):
    mask = jnp.arange(capacity) < (ln + 1)
    out = jax.vmap(lambda qh, kh, vh: pq_attention.exact_decode_attention(
        qh, kh, vh, mask, scale))(qq.reshape(h, 1, d), kk, vv)
    return out.reshape(h, d)

  fp32 = jax.vmap(oracle)(kf, vf, q, lengths)
  err_q4 = float(jnp.abs(got - fp32).max())
  cache8 = kvc.packed_exact_cache_prefill(k, v, capacity, 8)
  got8, _ = kvc.packed_exact_cache_append_and_attend(
      cache8, q, k_new, v_new, lengths, scale, 8, use_kernel=False,
      interpret=False)
  err_q8 = float(jnp.abs(got8 - fp32).max())
  assert 0 < err_q4 < 0.25, err_q4
  assert 0 < err_q8 < err_q4 / 4, (err_q8, err_q4)


def test_resident_q4_reconstruction_error_bounded(rng):
  """Prefill->dequant through the packed cache: per-element error obeys the
  per-group half-step bound computed from the *stored* scales — the bound
  the resident-q4 acceptance claim rests on."""
  b, h, n, d, bits = 2, 2, 24, 16, 4
  k = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
  v = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
  cache = kvc.packed_exact_cache_prefill(k, v, n, bits)
  k_hat, v_hat = kvc.packed_exact_dequant(cache, bits)
  group = packing.group_size(d)
  for x, x_hat, s in ((k, k_hat, cache.k_scale), (v, v_hat, cache.v_scale)):
    err = np.abs(np.asarray(x_hat) - np.asarray(x))
    err = err.reshape(b, h, n, d // group, group).max(-1)
    step = np.asarray(s, np.float32)
    absmax = np.abs(np.asarray(x)).reshape(
        b, h, n, d // group, group).max(-1)
    tol = 0.5 * step + F16_EPS * (15 * step + absmax) + 1e-12
    assert (err <= tol).all(), float((err - tol).max())
    assert err.max() > 0, "q4 should be lossy on continuous data"


# ---------------------------------------------------------------------------
# Policy dispatch, bytes accounting, engine-level token identity
# ---------------------------------------------------------------------------

def test_resident_codec_dispatches_to_packed_policy():
  packed = cache_registry.make("exact", _spec(kv_resident_codec="q4"))
  assert isinstance(packed, cache_api.PackedExactPolicy)
  assert packed.bits == 4
  assert not packed.prefix_shareable and packed.prefix_cacheable
  # every packed leaf crosses the tier boundary verbatim: codes and f16
  # headers are already the compressed form
  assert set(packed.spill_codecs()._asdict().values()) == {"raw"}
  dense = cache_registry.make("exact", _spec())
  assert type(dense) is cache_api.ExactPolicy


def test_spec_validates_resident_codec_with_valid_keys_listed():
  with pytest.raises(ValueError, match="kv_resident_codec.*q4"):
    _spec(kv_resident_codec="fp4")


def test_packed_bytes_hit_the_capacity_claim():
  """q4 resident store <= 0.30x the fp32 dense leaves at head_dim 16 — the
  ratio BENCH_serve.json records from PagedLayout.capacity_bytes."""
  d = 16
  packed = cache_registry.make("exact", _spec(kv_resident_codec="q4"))
  rep = packed.bytes(2, 2, d)
  assert rep["reduction_ratio"] > 1.0
  # leaf-level truth, independent of the bytes() fp16 baseline: sum actual
  # init nbytes vs the fp32 dense store
  q4_state = packed.init(2, 2, d)
  q4_bytes = sum(np.asarray(leaf).nbytes for leaf in q4_state)
  fp32_bytes = 2 * 2 * packed.spec.capacity * d * 4 * 2
  assert q4_bytes / fp32_bytes <= 0.30
  q8 = cache_registry.make("exact", _spec(kv_resident_codec="q8"))
  q8_bytes = sum(np.asarray(leaf).nbytes for leaf in q8.init(2, 2, d))
  assert q4_bytes < q8_bytes < fp32_bytes


def _cfg(**kw):
  return dataclasses.replace(get_arch("tinyllama-1.1b", reduced=True),
                             cache_policy="exact", dtype_str="float32", **kw)


@pytest.mark.parametrize("layout,sched,extra", [
    ("paged", "paged", dict(num_blocks=12)),
    ("tiered", "tiered", dict(num_blocks=5, host_blocks=16)),
])
def test_resident_q4_tokens_identical_across_dispatches(layout, sched, extra):
  """Greedy tokens from the packed Pallas(-interpret) kernel match the XLA
  reference bit-for-bit on the same params — on both pooled layouts (the
  tiered case also drives packed pages across the spill boundary)."""
  xla = ServeEngine(_cfg(kv_resident_codec="q4", decode_kernel="xla"),
                    context_len=64, max_batch=2, prompt_capacity=32,
                    cache_layout=layout, scheduler=sched, **extra)
  pal = ServeEngine(_cfg(kv_resident_codec="q4",
                         decode_kernel="pallas-interpret"),
                    context_len=64, max_batch=2, prompt_capacity=32,
                    params=xla.params, cache_layout=layout, scheduler=sched,
                    **extra)
  assert pal.layout.block_native
  trace = [(list(range(1, 21)), 14), (list(range(3, 25)), 14)]
  want = [xla.submit(p, max_new_tokens=m) for p, m in trace]
  got = [pal.submit(p, max_new_tokens=m) for p, m in trace]
  xla.run_to_completion()
  pal.run_to_completion()
  if layout == "tiered":
    assert pal.stats.spills >= 1, "trace never exercised the spill path"
  for w, g in zip(want, got):
    assert g.done and g.tokens == w.tokens, g.rid


# ---------------------------------------------------------------------------
# q5: fifth-bit mask plane (PR 9), both registries
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), d=st.sampled_from([8, 16, 64, 128]),
       n=st.integers(1, 9))
def test_pack_unpack_u5_roundtrip_exact(seed, d, n):
  rng = np.random.default_rng(seed)
  q = jnp.asarray(rng.integers(0, 32, size=(n, d)), jnp.uint8)
  p = packing.pack_u5(q)
  # low nibbles split-half (d/2 bytes) + fifth-bit plane (d/8 bytes)
  assert p.shape == (n, d // 2 + d // 8) and p.dtype == jnp.uint8
  np.testing.assert_array_equal(np.asarray(packing.unpack_u5(p)),
                                np.asarray(q, np.int32))


def test_q5_registered_with_intermediate_cost():
  assert packing.RESIDENT_CODECS["q5"] == 5
  assert isinstance(tiers.get_codec("q5"), tiers.Q5SpillCodec)
  # per-value cost sits strictly between q4 and q8 at every group width
  for d in (32, 64, 128):
    w4, w5, w8 = (packing.packed_width(d, b) for b in (4, 5, 8))
    assert w4 < w5 < w8, (d, w4, w5, w8)


def test_q5_spill_codec_between_q4_and_q8(rng):
  """One extra bit per code: q5 spill frames must be larger than q4 and
  smaller than q8, with reconstruction error strictly between them."""
  arr = rng.standard_normal((6, 70)).astype(np.float32)
  out = {}
  for key in ("q4", "q5", "q8"):
    payload, nbytes = tiers.get_codec(key).encode(arr)
    back = tiers.get_codec(key).decode(payload, arr.shape, arr.dtype)
    assert back.shape == arr.shape and back.dtype == arr.dtype
    out[key] = (nbytes, float(np.abs(back - arr).max()))
  assert out["q4"][0] < out["q5"][0] < out["q8"][0], out
  assert out["q4"][1] > out["q5"][1] > out["q8"][1], out
  # q5 halves q4's quantization step: the error bound scales accordingly
  assert out["q5"][1] < 0.6 * out["q4"][1], out


def test_q5_resident_store_between_q4_and_q8():
  sizes = {}
  for key in ("q4", "q5", "q8"):
    pol = cache_registry.make("exact", _spec(kv_resident_codec=key))
    sizes[key] = sum(np.asarray(leaf).nbytes for leaf in pol.init(2, 2, 16))
  assert sizes["q4"] < sizes["q5"] < sizes["q8"], sizes


# ---------------------------------------------------------------------------
# packed exact + prefix cache: the PR 8 interaction, pinned (PR 9)
# ---------------------------------------------------------------------------

def test_packed_exact_prefix_full_hit_oracle():
  """Full-prompt prefix hits over the packed (q4 resident) store must skip
  prefill without perturbing greedy tokens: the repeated prompt's stream is
  bit-identical to a cache-off oracle's."""
  cfg = _cfg(kv_resident_codec="q4", decode_kernel="pallas-interpret")
  off = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                    cache_layout="paged", scheduler="paged", num_blocks=12)
  on = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                   params=off.params, cache_layout="paged",
                   scheduler="prefix", num_blocks=12, prefix_cache=True)
  assert on.layout.block_native
  trace = [(list(range(1, 21)), 10), (list(range(1, 21)), 10)]
  want = [off.submit(p, max_new_tokens=m) for p, m in trace]
  got = [on.submit(p, max_new_tokens=m) for p, m in trace]
  off.run_to_completion()
  on.run_to_completion()
  assert on.stats.prefix_full_hits >= 1, on.stats
  assert on.stats.prefill_tokens < off.stats.prefill_tokens
  for w, g in zip(want, got):
    assert g.done and g.tokens == w.tokens, g.rid
