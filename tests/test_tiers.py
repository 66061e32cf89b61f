"""Tiered KV block pools: refcounted two-tier allocation, residency state
machine, spill codecs, tiered-vs-contiguous token oracles under forced
spill/fetch traffic, and the measured compressed-vs-raw transfer claim."""
import dataclasses

try:
  from hypothesis import given, settings, strategies as st
except ImportError:  # container lacks hypothesis: seeded fallback shim
  from hypothesis_compat import given, settings, strategies as st

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import cache_api, cache_registry, tiers
from repro.core import kv_cache as kvc
from repro.core import pq as pqlib
from repro.launch.engine import ServeEngine


def _cfg(policy="exact", dtype="float32", **kw):
  return dataclasses.replace(get_arch("tinyllama-1.1b", reduced=True),
                             cache_policy=policy, dtype_str=dtype, **kw)


def _pool_drained(layout):
  """Post-drain invariants: every block free on both tiers, no spill residue,
  all refcounts back to zero."""
  layout.manager.check_invariants()
  layout.pool.check()
  assert layout.free_blocks == layout.num_blocks
  assert layout.pool.allocated_count(tiers.DEVICE) == 0
  assert layout.pool.allocated_count(tiers.HOST) == 0
  assert not layout.records


# ---------------------------------------------------------------------------
# TieredBlockPool: refcounts, residency, LRU
# ---------------------------------------------------------------------------

def test_pool_refcounts_and_double_free():
  pool = tiers.TieredBlockPool(4, 2)
  ids = pool.alloc(2, owner="a")
  assert pool.refcount(ids[0]) == 1
  pool.ref(ids)                         # prefix-sharing groundwork
  assert pool.refcount(ids[0]) == 2
  assert pool.unref(ids, owner="a") == []          # refs 2 -> 1: not freed
  assert pool.free_count() == 2
  assert pool.unref(ids, owner="a") == ids         # refs 1 -> 0: freed
  assert pool.free_count() == 4
  with pytest.raises(ValueError):
    pool.unref(ids, owner="a")          # double free
  ids = pool.alloc(1, owner="a")
  with pytest.raises(ValueError):
    pool.unref(ids, owner="b")          # wrong owner
  # host tier is independent accounting
  h = pool.alloc(2, owner=7, tier=tiers.HOST)
  assert pool.free_count(tiers.HOST) == 0
  assert pool.alloc(1, owner=7, tier=tiers.HOST) is None
  pool.unref(h, owner=7, tier=tiers.HOST)
  pool.check()


def test_pool_residency_state_machine():
  pool = tiers.TieredBlockPool(4, 4)
  res = pool.alloc(1, owner=0)
  assert pool.state(res[0]) == tiers.BLOCK_RESIDENT
  inflight = pool.alloc(2, owner=("fetch", 9), state=tiers.BLOCK_IN_FLIGHT)
  with pytest.raises(AssertionError):
    pool.assert_state(inflight, tiers.BLOCK_RESIDENT)   # decode must not touch
  pool.set_state(inflight, tiers.BLOCK_RESIDENT)        # fetch completion
  pool.assert_state(inflight, tiers.BLOCK_RESIDENT)
  with pytest.raises(ValueError):
    pool.set_state(inflight, tiers.BLOCK_IN_FLIGHT)     # no reverse transition
  with pytest.raises(ValueError):
    pool.alloc(1, owner=1, state=tiers.BLOCK_SPILLED)   # illegal on device
  host = pool.alloc(1, owner=1, tier=tiers.HOST)
  assert pool.state(host[0], tiers.HOST) == tiers.BLOCK_SPILLED
  with pytest.raises(ValueError):
    pool.set_state(host, tiers.BLOCK_RESIDENT, tier=tiers.HOST)
  pool.check()


def test_pool_lru_cold_victim_order():
  pool = tiers.TieredBlockPool(6, 0)
  a = pool.alloc(2, owner="a")
  b = pool.alloc(2, owner="b")
  c = pool.alloc(2, owner="c")
  pool.touch(a)
  pool.touch(c)
  pool.touch(b)                          # b is hottest, a coldest of touched
  assert pool.lru_owner(["a", "b", "c"]) == "a"
  pool.touch(a)
  assert pool.lru_owner(["a", "b", "c"]) == "c"
  assert pool.lru_owner([]) is None
  # an owner with no blocks is colder than any touched owner
  assert pool.lru_owner(["b", "ghost"]) == "ghost"


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000), dev=st.integers(1, 12),
       host=st.integers(0, 12))
def test_pool_random_traffic_refcount_invariants(seed, dev, host):
  """Random alloc/ref/unref/spill-move traffic across both tiers: the pool
  never double-allocates, never leaks, and a full drain returns every
  refcount to zero."""
  rng = np.random.default_rng(seed)
  pool = tiers.TieredBlockPool(dev, host)
  held = {tiers.DEVICE: {}, tiers.HOST: {}}   # tier -> owner -> [(id, refs)]
  for _ in range(200):
    tier = int(rng.random() < 0.3) if host else tiers.DEVICE
    op = rng.random()
    if op < 0.45:
      owner = int(rng.integers(0, 3))
      n = int(rng.integers(0, pool.num_blocks[tier] + 1))
      ids = pool.alloc(n, owner=owner, tier=tier)
      in_use = sum(len(v) for v in held[tier].values())
      if n > pool.num_blocks[tier] - in_use:
        assert ids is None              # over-ask fails atomically
      else:
        assert ids is not None and len(ids) == n
        flat = [i for v in held[tier].values() for i, _ in v]
        assert not set(ids) & set(flat), "double allocation"
        if ids:
          held[tier].setdefault(owner, []).extend((i, 1) for i in ids)
    elif op < 0.6 and held[tier]:
      owner = list(held[tier])[int(rng.integers(0, len(held[tier])))]
      blocks = held[tier][owner]
      j = int(rng.integers(0, len(blocks)))
      pool.ref([blocks[j][0]], tier=tier)
      blocks[j] = (blocks[j][0], blocks[j][1] + 1)
    elif held[tier]:
      owner = list(held[tier])[int(rng.integers(0, len(held[tier])))]
      blocks = held[tier].pop(owner)
      keep = []
      for i, refs in blocks:
        freed = pool.unref([i], owner=owner, tier=tier)
        if refs > 1:
          assert freed == [], "freed while references remain"
          keep.append((i, refs - 1))
        else:
          assert freed == [i]
      if keep:
        held[tier][owner] = keep
    pool.check()
  for tier in (tiers.DEVICE, tiers.HOST):
    for owner, blocks in list(held[tier].items()):
      for i, refs in blocks:
        for _ in range(refs):
          pool.unref([i], owner=owner, tier=tier)
  pool.check()
  assert pool.allocated_count(tiers.DEVICE) == 0
  assert pool.allocated_count(tiers.HOST) == 0


# ---------------------------------------------------------------------------
# Spill codecs
# ---------------------------------------------------------------------------

def test_raw_codec_roundtrips_bit_exact(rng):
  import jax.numpy as jnp
  x = np.asarray(jnp.asarray(rng.normal(size=(3, 2, 8, 4)), jnp.bfloat16))
  enc, nb = tiers.get_codec("raw").encode(x)
  assert nb == x.nbytes
  out = tiers.get_codec("raw").decode(enc, x.shape, x.dtype)
  np.testing.assert_array_equal(np.asarray(out, np.float32),
                                np.asarray(x, np.float32))


def test_int8_codec_compresses_and_bounds_error(rng):
  x = rng.normal(size=(4, 2, 8, 16)).astype(np.float32)
  codec = tiers.get_codec("int8")
  enc, nb = codec.encode(x)
  assert nb < x.nbytes                  # actually smaller than raw f32
  out = codec.decode(enc, x.shape, np.float32)
  # 8-bit asymmetric quant: error bounded by half a step of the row range
  step = (x.max(-1) - x.min(-1)).max() / 255.0
  assert np.abs(out - x).max() <= step
  with pytest.raises(KeyError):
    tiers.get_codec("zstd")


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), key=st.sampled_from(["q4", "q8"]),
       count=st.sampled_from([1, 5, 31, 32, 33, 64, 321]),
       mag=st.sampled_from([1e-3, 1.0, 1e3]))
def test_packed_spill_codec_roundtrip_odd_tails(seed, key, count, mag):
  """q4/q8 over the flattened stream: byte accounting matches the block
  format exactly, tail groups (count not a multiple of 32) trim back to the
  original element count, and per-group error obeys the half-step bound
  (+ f16 header rounding) at any magnitude, negatives included."""
  rng = np.random.default_rng(seed)
  codec = tiers.get_codec(key)
  x = rng.normal(scale=mag, size=count).astype(np.float32)
  payload, nbytes = codec.encode(x)
  groups = -(-count // 32)
  assert nbytes == groups * (32 * codec.bits // 8 + 4)
  out = codec.decode(payload, (count,), np.float32)
  assert out.shape == (count,)
  pad = np.concatenate([x, np.repeat(x[-1], (-count) % 32)])
  xg = pad.reshape(groups, 32)
  step = (((xg.max(1) - xg.min(1)) / ((1 << codec.bits) - 1))
          .astype(np.float16).astype(np.float32))
  err = np.abs(out - x)
  # f16 header rounding is relative (2^-11) for normal halves and absolute
  # (2^-25) for subnormal ones, which small-magnitude groups land in
  qmax = (1 << codec.bits) - 1
  tol = (0.5 * step + 2 ** -11 * (step * qmax + np.abs(xg).max(1))
         + 2 ** -25 * (qmax + 1) + 1e-12)
  for g in range(groups):
    lo, hi = g * 32, min((g + 1) * 32, count)
    assert err[lo:hi].max() <= tol[g], (key, count, g)


def test_packed_spill_codec_beats_int8_on_real_rows(rng):
  """The PR 8 traffic claim at codec level: q4 moves < 0.55x the bytes int8
  moves on identical KV rows (f16 group headers amortize against int8's
  per-row f32 scale/zero), q8 lands between."""
  x = rng.normal(size=(4, 2, 8, 16)).astype(np.float32)
  size = {k: tiers.get_codec(k).encode(x)[1] for k in ("int8", "q4", "q8")}
  assert size["q4"] / size["int8"] < 0.55
  assert size["q4"] < size["q8"] < size["int8"] < x.nbytes


def test_spec_validates_spill_codec_and_policies_expose_codecs():
  with pytest.raises(ValueError, match="spill_codec"):
    cache_api.CacheSpec(capacity=64, head_dim=16, window=64,
                        spill_codec="gzip")
  spec = cache_api.CacheSpec(capacity=64, head_dim=16, window=32, sink=4,
                             recent=8, spill_codec="int8",
                             pq=kvc.PQCacheConfig(
                                 sink=4, recent=8, body_capacity=64,
                                 pq=pqlib.PQConfig(m=4, k=16)))
  exact = cache_registry.make("exact", spec)
  assert exact.spill_codecs() == kvc.ExactLayerCache(k="int8", v="int8")
  snap = cache_registry.make("snapkv", spec)
  # importance weights always spill raw (quantizing them would perturb
  # eviction choices across a swap)
  assert snap.spill_codecs().w == "raw"
  pq = cache_registry.make("pq", spec)
  # PQ code rows spill verbatim: they ARE the compressed representation
  assert pq.spill_codecs().key_indices == "raw"


# ---------------------------------------------------------------------------
# Tiered engine oracles: token-identical under forced spill/fetch
# ---------------------------------------------------------------------------

def test_tiered_spills_fetches_and_matches_contiguous_oracle():
  """Acceptance: traffic whose KV footprint exceeds the device pool
  completes under tiered+tiered via spill-to-host (KV preserved, zero
  recompute), token-identical to the contiguous run of the same trace."""
  cfg = _cfg()
  oracle = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32)
  tiered = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                       params=oracle.params, cache_layout="tiered",
                       scheduler="tiered", num_blocks=5, host_blocks=16)
  trace = [(list(range(1, 21)), 14), (list(range(3, 25)), 14)]
  want = [oracle.submit(p, max_new_tokens=m) for p, m in trace]
  got = [tiered.submit(p, max_new_tokens=m) for p, m in trace]
  oracle.run_to_completion()
  tiered.run_to_completion()

  assert tiered.stats.spills >= 1           # pool pressure actually hit
  assert tiered.stats.fetches == tiered.stats.spills
  assert tiered.stats.preempts == 0         # swap replaced recompute entirely
  assert sum(r.spill_count for r in got) == tiered.stats.spills
  for w, g in zip(want, got):
    assert g.done and g.tokens == w.tokens, g.rid
  led = tiered.layout.ledger
  assert led.spill_bytes > 0 and led.fetch_bytes == led.spill_bytes
  assert led.spill_blocks == led.fetch_blocks > 0
  assert tiered.stats.spill_bytes == led.spill_bytes
  assert tiered.stats.modeled_pcie_s == led.modeled_pcie_s > 0
  _pool_drained(tiered.layout)


def test_tiered_pq_codes_spill_and_match_oracle():
  """AQPIM pq over the tiered pool: code rows spill verbatim, resident
  rings/codebooks survive the swap bit-exactly, tokens match contiguous."""
  cfg = _cfg("pq", dtype="bfloat16")
  oracle = ServeEngine(cfg, context_len=96, max_batch=2, prompt_capacity=64)
  tiered = ServeEngine(cfg, context_len=96, max_batch=2, prompt_capacity=64,
                       params=oracle.params, cache_layout="tiered",
                       scheduler="tiered", num_blocks=7, host_blocks=32)
  trace = [(list(range(2, 60)), 24), (list(range(4, 49)), 24)]
  want = [oracle.submit(p, max_new_tokens=m) for p, m in trace]
  got = [tiered.submit(p, max_new_tokens=m) for p, m in trace]
  oracle.run_to_completion()
  tiered.run_to_completion()
  assert tiered.stats.spills >= 1
  for w, g in zip(want, got):
    assert g.done and g.tokens == w.tokens, g.rid
  _pool_drained(tiered.layout)


def test_tiered_random_traffic_oracle(rng):
  """Randomized admit/spill/fetch traffic under a tight pool: tokens stay
  identical to contiguous for every request, refcounts/residency clean."""
  cfg = _cfg()
  oracle = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32)
  tiered = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                       params=oracle.params, cache_layout="tiered",
                       scheduler="tiered", num_blocks=5, host_blocks=24)
  pairs = []
  for _ in range(7):
    plen = int(rng.integers(12, 30))
    gen = int(rng.integers(6, min(16, 64 - plen)))
    prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
    pairs.append((oracle.submit(prompt, max_new_tokens=gen),
                  tiered.submit(prompt, max_new_tokens=gen)))
  oracle.run_to_completion()
  tiered.run_to_completion()
  for w, g in pairs:
    assert g.tokens == w.tokens, (w.rid, w.tokens, g.tokens)
  assert tiered.stats.spills >= 1, "trace never exercised the spill path"
  _pool_drained(tiered.layout)


def test_fetch_ahead_starts_transfer_before_admit():
  """The one-step fetch-ahead hint: at least one swap-in's transfer starts
  (IN_FLIGHT) on the step before its admit finalizes it."""
  cfg = _cfg()
  eng = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                    cache_layout="tiered", scheduler="tiered",
                    num_blocks=5, host_blocks=16)
  eng.submit(list(range(1, 21)), max_new_tokens=14)
  eng.submit(list(range(3, 25)), max_new_tokens=14)
  eng.run_to_completion()
  assert eng.stats.fetches >= 1
  assert eng.stats.prefetches >= 1
  assert eng.stats.prefetches <= eng.stats.fetches
  _pool_drained(eng.layout)


def test_int8_spill_codec_end_to_end_compresses():
  """Opt-in int8 exact-KV spilling: completes, and the ledger shows the
  boundary traffic genuinely below the raw equivalent."""
  cfg = _cfg(spill_codec="int8")
  eng = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                    cache_layout="tiered", scheduler="tiered",
                    num_blocks=5, host_blocks=16)
  a = eng.submit(list(range(1, 21)), max_new_tokens=14)
  b = eng.submit(list(range(3, 25)), max_new_tokens=14)
  eng.run_to_completion()
  assert a.done and b.done
  led = eng.layout.ledger
  assert eng.stats.spills >= 1
  assert led.compression_ratio < 1.0
  assert led.spill_bytes < led.spill_raw_bytes
  _pool_drained(eng.layout)


@pytest.mark.parametrize("codec,max_ratio", [("q4", 0.20), ("q8", 0.32)])
def test_packed_spill_codec_token_identity_vs_oracle(codec, max_ratio):
  """Sub-byte spill under forced spill/fetch traffic: greedy tokens stay
  identical to the contiguous oracle on this trace (the lossy roundtrip
  only touches spilled-and-fetched blocks, and its half-step perturbation
  does not flip any argmax here), while the ledger shows the boundary
  traffic at the analytic packed fraction of raw f32 (q4 0.15625,
  q8 0.28125 — block leaves divide evenly into 32-groups, no tail)."""
  cfg = _cfg(spill_codec=codec)
  oracle = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32)
  tiered = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                       params=oracle.params, cache_layout="tiered",
                       scheduler="tiered", num_blocks=5, host_blocks=16)
  trace = [(list(range(1, 21)), 14), (list(range(3, 25)), 14)]
  want = [oracle.submit(p, max_new_tokens=m) for p, m in trace]
  got = [tiered.submit(p, max_new_tokens=m) for p, m in trace]
  oracle.run_to_completion()
  tiered.run_to_completion()
  assert tiered.stats.spills >= 1, "trace never exercised the spill path"
  for w, g in zip(want, got):
    assert g.done and g.tokens == w.tokens, g.rid
  led = tiered.layout.ledger
  assert led.spill_bytes < max_ratio * led.spill_raw_bytes
  assert led.fetch_bytes == led.spill_bytes
  _pool_drained(tiered.layout)


def test_tiered_falls_back_to_recompute_when_host_pool_full():
  """Graceful degradation: a host tier too small to hold the victim's KV
  falls back to PR 2 recompute preemption instead of wedging — still
  finishing with correct tokens."""
  cfg = _cfg()
  oracle = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32)
  tiered = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                       params=oracle.params, cache_layout="tiered",
                       scheduler="tiered", num_blocks=5, host_blocks=1)
  trace = [(list(range(1, 21)), 14), (list(range(3, 25)), 14)]
  want = [oracle.submit(p, max_new_tokens=m) for p, m in trace]
  got = [tiered.submit(p, max_new_tokens=m) for p, m in trace]
  oracle.run_to_completion()
  tiered.run_to_completion()
  assert tiered.stats.preempts >= 1     # recompute path taken
  for w, g in zip(want, got):
    assert g.done and g.tokens == w.tokens, g.rid
  _pool_drained(tiered.layout)


def test_tiered_scheduler_requires_tiered_layout():
  with pytest.raises(ValueError, match="tiered"):
    ServeEngine(_cfg(), context_len=64, max_batch=1, prompt_capacity=16,
                cache_layout="paged", scheduler="tiered")


# ---------------------------------------------------------------------------
# The measured communication claim (paper abstract / Fig. 13)
# ---------------------------------------------------------------------------

def test_pq_spill_traffic_under_quarter_of_exact_raw():
  """Acceptance: on an identical forced-spill trace, AQPIM pq moves < 25%
  of the bytes across the tier boundary that raw exact KV moves — the same
  numbers benchmarks/run.py --json records into BENCH_serve.json."""
  from benchmarks.run import run_tiered_transfer
  rec = run_tiered_transfer("tinyllama-1.1b")
  assert rec["policies"]["exact"]["spills"] >= 1
  assert rec["policies"]["pq"]["spills"] >= 1
  assert rec["pq_vs_exact_raw_spill"] is not None
  assert rec["pq_vs_exact_raw_spill"] < 0.25
