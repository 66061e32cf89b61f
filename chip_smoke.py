#!/usr/bin/env python3
"""Chip smoke test: the AQPIM serve path on a TPU at full width.

  python chip_smoke.py            # one chip: phases (a)-(e) below
  python chip_smoke.py --mesh 4   # four chips: only the sharded-serving check

Everything runs in this one process (a chip belongs to one process at a
time) through the entry points a user calls: `serve.make_parser()` +
`serve.build_engine()` for the continuous-batching engine, `ServeRun` for the
fixed-batch path.  The model is `tinyllama-1.1b` at its published widths
(22 layers, d_model 2048, 32/4 heads of 64, vocab 32000, bf16) with random
weights from `--seed`.

One chip:
  (a) the device is a TPU, or the script exits non-zero (no CPU path);
  (b) the pq paged engine (`--cache-policy pq --cache-layout paged
      --scheduler paged --decode-kernel auto`) serves mixed prompt lengths up
      to ~1k tokens: `auto` resolves to compiled `pallas`, decode is
      block-native (0 dense-materialized bytes per step) and every request
      gets its tokens; the same requests through `--decode-kernel xla` give
      the pallas-vs-xla greedy-token agreement (printed, not gated: random
      weights make near-ties that either side may break);
  (c) the same for `--cache-policy exact` (paged flash decode);
  (d) the fixed-batch `ServeRun` path with pq (the dense PQ kernel);
  (e) kernel parity at full width, compiled Pallas against the XLA
      references run at `Precision.HIGHEST`, for the dense and paged PQ
      kernels and the paged exact kernel.

Parity tolerance: |pallas - xla| <= 2^-8 * max|V| on the outputs and the
softmax denominators, with V the values attended over.
  * Exact kernel: q, K and V are bf16, the serve dtype.  Its dots run at the
    MXU's default precision, one bf16 pass with f32 accumulation: q.K is
    exact (bf16 operands), but the f32 probabilities are rounded to bf16
    (relative error <= 2^-9) before p.V, so an output moves by at most
    2^-9 * max|V|.  The bound allows twice that.
  * PQ kernels: the table is f32 on the VPU and the one-hot lookups run at
    Precision.HIGHEST, so each selected table entry and bucket sum keeps at
    least 16 mantissa bits; over m=32 subvectors a score moves by at most
    32 * 2^-17 * max|T|, which at these magnitudes (|T| < 4) moves an output
    by well under 2^-8 * max|V|.

With `--mesh 4` the script runs only the pq paged engine with
`--mesh-model 4` (kv heads split four ways) and the one-device engine on the
same params and prompts; their greedy tokens must be identical.

The last line of standard output is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "tinyllama-1.1b"
REDUCED = False            # the published widths
EXPECT_KERNEL = "pallas"   # what `--decode-kernel auto` must resolve to
BATCH = 4
PROMPT_CAP = 1024
PROMPT_LENS = (1000, 517, 130, 33, 800, 260)
GEN = 16
SERVE_RUN = dict(batch=4, prompt_len=512, gen=16)
PARITY = dict(b=4, h=4, g=8, d=64, m=32, k=512, n=1024, block=16)
TOL = 2.0 ** -8


def log(msg: str) -> None:
  print(msg, flush=True)


def check_device(count: int):
  """(a) The first device must be a TPU, and there must be `count` of them."""
  import jax
  devices = jax.devices()
  if devices[0].platform != "tpu":
    sys.exit(f"chip_smoke: no TPU found — JAX reports platform "
             f"{devices[0].platform!r}; this script runs only on a TPU")
  if len(devices) < count:
    sys.exit(f"chip_smoke: needs {count} TPU devices, JAX reports "
             f"{len(devices)}")
  log(f"device: {devices[0].device_kind} x{len(devices)} "
      f"(platform {devices[0].platform})")
  return devices


def engine_args(policy: str, kernel: str = "auto", mesh_model=None):
  from repro.launch import serve
  argv = ["--arch", ARCH, "--cache-policy", policy, "--cache-layout", "paged",
          "--scheduler", "paged", "--decode-kernel", kernel,
          "--batch", str(BATCH), "--prompt-len", str(PROMPT_CAP),
          "--gen", str(GEN)]
  if REDUCED:
    argv.append("--reduced")
  if mesh_model:
    argv += ["--mesh-model", str(mesh_model)]
  return serve.make_parser().parse_args(argv)


def make_prompts(vocab: int, seed: int):
  rng = np.random.default_rng(seed)
  return [rng.integers(1, vocab, size=n).tolist() for n in PROMPT_LENS]


def run_engine(label: str, args, prompts):
  """Build the engine the CLI flags describe and serve `prompts` to the end.

  A short warm-up request compiles the prefill, admission and decode
  programs first, so the timed run measures execution.
  """
  from repro.launch import serve
  t0 = time.perf_counter()
  engine = serve.build_engine(args)
  engine.submit(prompts[0][:8], max_new_tokens=2)
  engine.run_to_completion()
  compile_s = time.perf_counter() - t0
  handles = [engine.submit(p, max_new_tokens=GEN) for p in prompts]
  t1 = time.perf_counter()
  engine.run_to_completion()
  run_s = time.perf_counter() - t1
  tokens = [list(h.tokens) for h in handles]
  n_tok = sum(len(t) for t in tokens)
  kernel = engine.model.cache_policy.effective_decode_kernel
  traffic = engine.layout.decode_traffic
  log(f"{label}: kernel {kernel}, {traffic['decode_path']} decode, "
      f"dense materialized {traffic['dense_materialized_bytes_per_step']} "
      f"B/step; build+compile {compile_s:.1f} s, run {run_s:.2f} s, "
      f"{len(handles)} requests, {n_tok} tokens, "
      f"{n_tok / run_s:.1f} tok/s")
  assert all(h.done and not h.failed for h in handles), label
  assert all(len(t) == GEN for t in tokens), (label, [len(t) for t in tokens])
  vocab = engine.cfg.vocab_size
  assert all(0 <= x < vocab for t in tokens for x in t), label
  result = dict(tokens=tokens, kernel=kernel, traffic=traffic)
  del engine, handles
  gc.collect()
  return result


def agreement(label: str, a, b) -> None:
  same_req = sum(x == y for x, y in zip(a, b))
  same_tok = sum(p == q for x, y in zip(a, b) for p, q in zip(x, y))
  total = sum(len(x) for x in a)
  log(f"{label}: {same_req}/{len(a)} requests token-identical, "
      f"{same_tok}/{total} tokens equal")


def phase_engine(policy: str, prompts, with_xla: bool):
  """(b)/(c): the block-native pallas engine, optionally against xla."""
  got = run_engine(f"({'b' if policy == 'pq' else 'c'}) {policy} engine",
                   engine_args(policy), prompts)
  assert got["kernel"] == EXPECT_KERNEL, got["kernel"]
  assert got["traffic"]["dense_materialized_bytes_per_step"] == 0
  if with_xla:
    ref = run_engine(f"    {policy} engine, xla",
                     engine_args(policy, kernel="xla"), prompts)
    agreement(f"    {policy} pallas-vs-xla greedy tokens", got["tokens"],
              ref["tokens"])


def phase_serve_run(seed: int) -> None:
  """(d): the fixed-batch ServeRun path (contiguous cache, dense PQ kernel)."""
  from repro.configs import get_arch
  from repro.launch.serve import ServeRun
  t0 = time.perf_counter()
  res = ServeRun(arch=ARCH, reduced=REDUCED, cache_policy="pq",
                 decode_kernel="auto", measure_latency=False, seed=seed,
                 **SERVE_RUN).run()
  tokens = np.asarray(res["tokens"])
  vocab = get_arch(ARCH, reduced=REDUCED).vocab_size
  log(f"(d) ServeRun pq: kernel {res['decode_kernel']}, prefill "
      f"{res['prefill_s']:.3f} s, decode {res['decode_s']:.3f} s "
      f"({res['tok_per_s']:.1f} tok/s), total with compile "
      f"{time.perf_counter() - t0:.1f} s")
  assert res["decode_kernel"] == EXPECT_KERNEL, res["decode_kernel"]
  assert tokens.shape == (SERVE_RUN["batch"], SERVE_RUN["gen"]), tokens.shape
  assert (tokens >= 0).all() and (tokens < vocab).all()


def _check(label: str, got, want, vmax: float) -> None:
  got = np.asarray(got, np.float64)
  want = np.asarray(want, np.float64)
  assert np.isfinite(got).all(), label
  err = float(np.abs(got - want).max())
  bound = TOL * vmax
  log(f"    {label}: max |pallas - xla| {err:.3e} (bound {bound:.3e})")
  assert err <= bound, (label, err, bound)


def phase_parity(seed: int) -> None:
  """(e): compiled kernels against the XLA references, at full width."""
  import jax
  import jax.numpy as jnp

  from repro.core import pq_attention
  from repro.kernels import ops, ref

  p = PARITY
  b, h, g, d, m, k, n, blk = (p[x] for x in
                              ("b", "h", "g", "d", "m", "k", "n", "block"))
  dsub = d // m
  nb = n // blk
  key = jax.random.PRNGKey(seed)
  ks = jax.random.split(key, 8)
  bf16 = jnp.bfloat16
  q = jax.random.normal(ks[0], (b, h, g, d), bf16)
  # codebooks are stored bf16 in the cache and widened to f32 at use
  kcb = jax.random.normal(ks[1], (b, h, m, k, dsub), bf16).astype(jnp.float32)
  vcb = jax.random.normal(ks[2], (b, h, m, k, dsub), bf16).astype(jnp.float32)
  lengths = jnp.asarray(np.linspace(n // 3, n, b).astype(np.int32))
  scale = d ** -0.5
  t0 = time.perf_counter()

  # pool of b * nb blocks + a trash block, one layer; tables are a
  # permutation so logical order differs from pool order
  perm = np.random.default_rng(seed).permutation(b * nb).astype(np.int32)
  tables = jnp.asarray(perm.reshape(b, nb))
  kidx_pool = jax.random.randint(ks[3], (b * nb + 1, 1, h, blk, m), 0, k,
                                 jnp.int16)
  vidx_pool = jax.random.randint(ks[4], (b * nb + 1, 1, h, blk, m), 0, k,
                                 jnp.int16)

  def dense(pool):
    """(P+1, 1, H, blk, X) pool -> (B, H, N, X) logical view."""
    x = pool[perm, 0]                                  # logical block order
    return jnp.moveaxis(x.reshape(b, nb, h, blk, -1), 2, 1).reshape(
        b, h, n, -1)

  kidx = dense(kidx_pool).astype(jnp.int32)
  vidx = dense(vidx_pool).astype(jnp.int32)
  vmax = float(jnp.abs(vcb).max())

  with jax.default_matmul_precision("highest"):
    r_out, r_stats = ref.pq_decode_attention_ref(
        q.reshape(b * h, g, d), kcb.reshape(b * h, m, k, dsub),
        vcb.reshape(b * h, m, k, dsub), kidx.reshape(b * h, n, m),
        vidx.reshape(b * h, n, m), jnp.repeat(lengths, h), scale)
  r_out = r_out.reshape(b, h, g, d)
  r_den = r_stats[:, 1].reshape(b, h, g)

  out, _, den = ops.pq_decode_attention(
      q, kcb, vcb, kidx, vidx, jnp.broadcast_to(lengths[:, None], (b, h)),
      scale, blk=ops.decode_block(n))
  _check("pq dense out", out, r_out, vmax)
  _check("pq dense denom", den / r_den, np.ones(r_den.shape), 1.0)
  out, _, den = ops.pq_decode_attention_paged(
      q, kcb, vcb, kidx_pool, vidx_pool, tables, jnp.asarray(0, jnp.int32),
      lengths, scale)
  _check("pq paged out", out, r_out, vmax)
  _check("pq paged denom", den / r_den, np.ones(r_den.shape), 1.0)

  k_pool = jax.random.normal(ks[5], (b * nb + 1, 1, h, blk, d), bf16)
  v_pool = jax.random.normal(ks[6], (b * nb + 1, 1, h, blk, d), bf16)
  kd, vd = dense(k_pool), dense(v_pool)
  with jax.default_matmul_precision("highest"):
    want = jax.vmap(lambda qb, kb, vb, ln: jax.vmap(
        lambda qq, kk, vv: pq_attention.exact_decode_attention(
            qq, kk, vv, jnp.arange(n) < ln, scale))(qb, kb, vb))(
                q, kd, vd, lengths)
  got = ops.paged_flash_decode(q, k_pool, v_pool, tables,
                               jnp.asarray(0, jnp.int32), lengths, scale)
  _check("exact paged out", got, want, float(jnp.abs(v_pool).max()))
  _exact_step_parity(seed)
  log(f"(e) kernel parity at full width: ok in "
      f"{time.perf_counter() - t0:.1f} s")


def _exact_step_parity(seed: int) -> None:
  """The exact paged step at head dim 128, where the kernel writes each new
  row by whole-page write-back into pools aliased in place, against an XLA
  row scatter before the read: the same output and the same pools, bit for
  bit, on every block but the trash block."""
  import jax
  import jax.numpy as jnp

  from repro.core import kv_cache as kvc
  from repro.kernels import ops

  h, g, d, blk, nb = 8, 7, 128, 16, 40
  lengths = np.asarray([0, 15, 32, 37, 255, 256, nb * blk - 1], np.int32)
  b = len(lengths)
  trash = b * nb
  rng = np.random.default_rng(seed)
  tables = rng.permutation(trash).reshape(b, nb).astype(np.int32)
  for i, n in enumerate(lengths):
    tables[i, n // blk + 1 if n else 0:] = trash
  ks = jax.random.split(jax.random.PRNGKey(seed), 5)
  bf16 = jnp.bfloat16
  k_pool, v_pool = (jax.random.normal(k, (trash + 1, 2, h, blk, d), bf16)
                    for k in ks[:2])
  q = jax.random.normal(ks[2], (b, h * g, d), bf16)
  k_new, v_new = (jax.random.normal(k, (b, h, d), bf16) for k in ks[3:])
  tables, lengths, layer = (jnp.asarray(tables), jnp.asarray(lengths),
                            jnp.asarray(1, jnp.int32))

  @jax.jit
  def scatter_then_read(k_pool, v_pool):
    pids = tables[jnp.arange(b), lengths // blk]
    k_pool = k_pool.at[pids, layer, :, lengths % blk].set(k_new)
    v_pool = v_pool.at[pids, layer, :, lengths % blk].set(v_new)
    out = ops.paged_flash_decode(q.reshape(b, h, g, d), k_pool, v_pool,
                                 tables, layer, lengths + 1, d ** -0.5)
    return out.reshape(b, h * g, d), k_pool, v_pool

  want = scatter_then_read(k_pool, v_pool)
  got = jax.jit(lambda kp, vp: kvc.exact_cache_paged_step(
      kp, vp, layer, tables, q, k_new, v_new, lengths, d ** -0.5,
      interpret=False))(k_pool, v_pool)
  for label, x, y in zip(("out", "k_pool", "v_pool"), got, want):
    x, y = np.asarray(x[:trash], np.float32), np.asarray(y[:trash],
                                                         np.float32)
    np.testing.assert_array_equal(x, y, err_msg=label)
  log("    exact paged step at d=128 (kernel-written row): output and "
      "pools bit-identical to a scatter")


def phase_mesh(prompts, shards: int) -> None:
  """--mesh N: the heads-sharded pq engine against the one-device engine."""
  one = run_engine("one-device pq engine", engine_args("pq"), prompts)
  sharded = run_engine(f"{shards}-way pq engine",
                       engine_args("pq", mesh_model=shards), prompts)
  agreement(f"mesh {shards} vs one device greedy tokens", sharded["tokens"],
            one["tokens"])
  assert sharded["kernel"] == one["kernel"] == EXPECT_KERNEL
  assert sharded["tokens"] == one["tokens"], "sharded tokens diverged"


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--mesh", type=int, default=None, metavar="N",
                  help="run only the N-way sharded-serving check (N chips)")
  ap.add_argument("--seed", type=int, default=0,
                  help="seed of the random weights, prompts and parity "
                       "inputs")
  args = ap.parse_args(argv)
  devices = check_device(args.mesh or 1)

  import jax
  from repro.common import compile_cache
  from repro.configs import get_arch
  cache_dir = compile_cache.enable()
  log(f"compile cache: {cache_dir}")
  prompts = make_prompts(get_arch(ARCH, reduced=REDUCED).vocab_size,
                         args.seed)
  t0 = time.perf_counter()
  if args.mesh:
    phase_mesh(prompts, args.mesh)
  else:
    phase_engine("pq", prompts, with_xla=True)
    phase_engine("exact", prompts, with_xla=False)
    phase_serve_run(args.seed)
    phase_parity(args.seed)
  log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
  print(json.dumps({"ok": True, "device": {
      "platform": devices[0].platform, "kind": devices[0].device_kind,
      "count": len(jax.devices())}}))
  return 0


if __name__ == "__main__":
  sys.exit(main())
