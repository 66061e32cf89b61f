"""Benchmark harness: one function per paper table/figure.

Default mode prints ``name,us_per_call,derived`` CSV.  Roofline rows require
the dry-run JSONs (python -m repro.launch.dryrun); other benches are
self-contained.

``--json`` instead runs the serving benchmark (tinyllama reduced, `pq` vs
`exact` cache policy through `repro.launch.serve.ServeRun`) and *appends* a
timestamped record to ``BENCH_serve.json`` (``{"runs": [...]}``), so the
serving perf trajectory accumulates across PRs instead of overwriting.
Each record carries the axes that now exist (`cache_layout` / `scheduler` /
`kv_block_size`, plus the git SHA) and a ``tiered`` section: a forced-spill
trace through the tiered (device+host) engine per policy, reporting the
`TransferLedger` tier-boundary bytes — the paper's compressed-vs-raw
communication claim as a measured quantity (`pq_vs_exact_raw_spill` is the
pq spill traffic as a fraction of exact raw spill traffic on an identical
trace).

Since PR 5 every policy row also records per-step decode latency
percentiles (p50/p99) and a ``decode_kernels`` section: the paged engine
driven under `--decode-kernel xla` vs `pallas-interpret` on one trace,
asserting greedy-token identity and recording the modeled decode HBM bytes
per step — dense gather->decode->scatter vs block-table-native pool reads
(`pq_block_native_dense_bytes` must be 0: the kernels read paged storage in
place).

Since PR 8 a ``packed`` section measures the sub-byte KV codecs: q4/q8
spill traffic vs int8 on the forced-spill trace, and the resident-q4
exact policy's pool footprint + kernel-vs-XLA greedy-token identity; the
trajectory file keeps only the newest ``BENCH_HISTORY_KEEP`` records.

Since PR 9 a ``recovery`` section measures fault-tolerant serving: SLO
shedding vs stalling goodput on an overload trace, corrupted-spill-page
recovery (survivor tokens bit-identical to the fault-free oracle), and
prefix-cache snapshot/restore (a restarted engine's warm hit tokens beat
cold).  The trajectory file itself is written atomically (temp +
``os.replace``) so a crashed bench never leaves a torn history.
"""
import argparse
import json
import os
import subprocess
import sys
import time

from repro.common import compile_cache


def run_csv() -> int:
  from benchmarks import (
      fig10_tradeoff,
      fig11_13_latency_model,
      table2_table3_sweeps,
      table4_ablation,
      table5_indirection,
  )
  from benchmarks import roofline

  print("name,us_per_call,derived")
  modules = [
      ("table2/3", table2_table3_sweeps),
      ("table4", table4_ablation),
      ("fig10", fig10_tradeoff),
      ("fig11-13", fig11_13_latency_model),
      ("table5", table5_indirection),
      ("roofline", roofline),
  ]
  failures = 0
  for name, mod in modules:
    try:
      for line in mod.run():
        print(line)
    except Exception as e:  # noqa: BLE001
      failures += 1
      print(f"{name}_ERROR,0.0,{type(e).__name__}:{e}")
  return 1 if failures else 0


def _load_history(out_path: str) -> list:
  """Existing run records; a legacy single-record file becomes run 0.

  An unparseable file is moved aside (never silently dropped — it is the
  accumulated perf trajectory this mode exists to preserve)."""
  if not os.path.exists(out_path):
    return []
  try:
    with open(out_path) as f:
      prev = json.load(f)
  except (OSError, ValueError) as e:
    backup = out_path + ".corrupt"
    os.replace(out_path, backup)
    print(f"WARNING: could not parse {out_path} ({e}); "
          f"moved it to {backup} and starting a fresh trajectory")
    return []
  if isinstance(prev, dict) and isinstance(prev.get("runs"), list):
    return prev["runs"]
  if isinstance(prev, dict) and prev:
    return [prev]
  return []


def _git_sha() -> str:
  try:
    return subprocess.check_output(
        ["git", "rev-parse", "--short", "HEAD"],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        text=True, stderr=subprocess.DEVNULL).strip()
  except Exception:  # noqa: BLE001  (not a git checkout / no git binary)
    return "unknown"


def run_tiered_transfer(arch: str = "tinyllama-1.1b", prompt_len: int = 352,
                        gen: int = 48, block: int = 16, num_blocks: int = 46,
                        host_blocks: int = 192) -> dict:
  """Forced-spill trace through the tiered engine, per policy.

  The pool is sized to co-admit two long requests but not their decode
  growth, so each run swaps one victim to the host tier and fetches it
  back — making the tier-boundary bytes a measured, not modeled, quantity.
  Identical traffic for both policies; only the spilled representation
  differs (PQ code rows + resident rings/codebooks vs raw exact KV).
  """
  import dataclasses
  from repro.configs import get_arch
  from repro.launch.engine import ServeEngine

  out = {"cache_layout": "tiered", "scheduler": "tiered",
         "kv_block_size": block, "num_blocks": num_blocks,
         "host_blocks": host_blocks, "batch": 2, "prompt_len": prompt_len,
         "gen": gen, "policies": {}}
  for policy in ("pq", "exact"):
    cfg = dataclasses.replace(
        get_arch(arch, reduced=True), cache_policy=policy,
        dtype_str="bfloat16", cache_layout="tiered", scheduler="tiered",
        kv_block_size=block)
    eng = ServeEngine(cfg, context_len=prompt_len + gen, max_batch=2,
                      prompt_capacity=prompt_len, num_blocks=num_blocks,
                      host_blocks=host_blocks)
    for i in range(2):
      eng.submit([7 + i] * (prompt_len - 8 * i), max_new_tokens=gen)
    eng.run_to_completion()
    led = eng.layout.ledger
    by = eng.layout.bytes()
    out["policies"][policy] = {
        "spills": eng.stats.spills, "fetches": eng.stats.fetches,
        "prefetches": eng.stats.prefetches,
        "spill_bytes": led.spill_bytes,
        "spill_raw_bytes": led.spill_raw_bytes,
        "fetch_bytes": led.fetch_bytes,
        "modeled_pcie_s": round(led.modeled_pcie_s, 6),
        "layout_bytes": by,
    }
    print(f"tiered[{policy}]: {eng.stats.spills} spills "
          f"({led.spill_bytes} B), {eng.stats.fetches} fetches "
          f"({led.fetch_bytes} B)")
  exact_raw = out["policies"]["exact"]["spill_raw_bytes"]
  pq_bytes = out["policies"]["pq"]["spill_bytes"]
  out["pq_vs_exact_raw_spill"] = (
      round(pq_bytes / exact_raw, 4) if exact_raw else None)
  print(f"tiered: pq spill traffic = "
        f"{out['pq_vs_exact_raw_spill']} of exact raw")
  return out


def run_prefix_trace(arch: str = "tinyllama-1.1b", prompt_len: int = 64,
                     gen: int = 16, block: int = 16, num_blocks: int = 24,
                     users: int = 4, repeats: int = 2) -> dict:
  """Shared-prefix serving trace through the prefix cache, per policy.

  N users share one long system prompt with distinct short suffixes, plus
  `repeats` exact resubmissions (retry/regenerate traffic).  Each policy
  runs the identical trace twice — prefix cache off, then on — asserting
  token-identical outputs and recording what the cache saved: prefill
  tokens computed, hit rate, peak *mapped* KV blocks (a shared block counts
  once), dedup bytes, and COW forks.  `pq_vs_exact_block_bytes` is the
  footprint of one shared-prefix block under AQPIM PQ codes vs exact KV —
  the reason one cached prefix serves many more users inside the same
  device pool.
  """
  import dataclasses
  from repro.configs import get_arch
  from repro.launch.engine import ServeEngine

  sys_prompt = list(range(3, 3 + prompt_len - block))   # whole shared blocks
  trace = [(sys_prompt + [997 - 7 * u] * (block // 2), gen)
           for u in range(users)]
  trace += [trace[u % users] for u in range(repeats)]   # exact resubmits
  out = {"cache_layout": "paged", "kv_block_size": block,
         "num_blocks": num_blocks, "batch": 2, "prompt_len": prompt_len,
         "gen": gen, "users": users, "repeats": repeats, "policies": {}}
  for policy in ("pq", "exact"):
    cfg = dataclasses.replace(
        get_arch(arch, reduced=True), cache_policy=policy,
        dtype_str="bfloat16", cache_layout="paged", kv_block_size=block)
    off = ServeEngine(cfg, context_len=prompt_len + gen, max_batch=2,
                      prompt_capacity=prompt_len, num_blocks=num_blocks,
                      scheduler="paged")
    on = ServeEngine(cfg, context_len=prompt_len + gen, max_batch=2,
                     prompt_capacity=prompt_len, num_blocks=num_blocks,
                     scheduler="prefix", prefix_cache=True,
                     params=off.params)
    want = [off.submit(p, max_new_tokens=m) for p, m in trace]
    got = [on.submit(p, max_new_tokens=m) for p, m in trace]
    off.run_to_completion()
    on.run_to_completion()
    identical = all(w.tokens == g.tokens for w, g in zip(want, got))
    by_on = on.layout.bytes()
    by_off = off.layout.bytes()
    saved = 1.0 - (on.stats.prefill_tokens
                   / max(off.stats.prefill_tokens, 1))
    out["policies"][policy] = {
        "tokens_identical": identical,
        "prefill_tokens_nocache": off.stats.prefill_tokens,
        "prefill_tokens": on.stats.prefill_tokens,
        "prefill_tokens_saved_frac": round(saved, 4),
        "prefix_hits": on.stats.prefix_hits,
        "prefix_full_hits": on.stats.prefix_full_hits,
        "prefix_hit_rate": round(on.stats.prefix_hit_rate, 4),
        "forked_blocks": on.stats.forked_blocks,
        "dedup_bytes": on.stats.dedup_bytes,
        "block_bytes": by_on["block_bytes"],
        "peak_mapped_blocks": by_on["peak_mapped_blocks"],
        "peak_mapped_blocks_nocache": by_off["peak_mapped_blocks"],
        "peak_mapped_bytes": by_on["peak_mapped_bytes"],
        "peak_mapped_bytes_nocache": by_off["peak_mapped_bytes"],
    }
    print(f"prefix[{policy}]: prefill tokens {off.stats.prefill_tokens} -> "
          f"{on.stats.prefill_tokens} ({100 * saved:.0f}% saved), hit rate "
          f"{on.stats.prefix_hit_rate:.2f}, peak mapped blocks "
          f"{by_off['peak_mapped_blocks']} -> {by_on['peak_mapped_blocks']}"
          f"{'' if identical else '  TOKENS DIVERGED'}")
  exact_bb = out["policies"]["exact"]["block_bytes"]
  pq_bb = out["policies"]["pq"]["block_bytes"]
  out["pq_vs_exact_block_bytes"] = (round(pq_bb / exact_bb, 4)
                                    if exact_bb else None)
  print(f"prefix: pq shared-prefix block footprint = "
        f"{out['pq_vs_exact_block_bytes']} of exact")
  return out


def run_decode_kernels(arch: str = "tinyllama-1.1b", prompt_len: int = 32,
                       gen: int = 16, block: int = 16) -> dict:
  """Paged-engine decode trace per policy x decode kernel.

  Runs the identical staggered trace through the paged engine under the
  `xla` dispatch (dense gather->decode->scatter) and `pallas-interpret`
  (block-table-native kernels), asserting greedy-token identity and
  recording per-step latency percentiles plus the modeled decode HBM bytes
  (`CacheLayout.decode_traffic_model`).  The headline figure: under the
  block-native path the paged pq decode's dense-materialization bytes are 0
  — the kernel streams table-mapped pool blocks in place.  (Interpret-mode
  wall clock is not meaningful perf — the model figures are the comparison;
  on TPU the same record carries compiled-kernel numbers.)
  """
  import dataclasses
  from repro.common.timing import Stopwatch
  from repro.configs import get_arch
  from repro.launch.engine import ServeEngine

  out = {"cache_layout": "paged", "scheduler": "paged",
         "kv_block_size": block, "batch": 2, "prompt_len": prompt_len,
         "gen": gen, "policies": {}}
  trace = [(list(range(3, 3 + prompt_len - 4 * i)), gen) for i in range(4)]
  for policy in ("pq", "exact"):
    out["policies"][policy] = {}
    params = None
    toks = {}
    for kern in ("xla", "pallas-interpret"):
      cfg = dataclasses.replace(
          get_arch(arch, reduced=True), cache_policy=policy,
          dtype_str="bfloat16", cache_layout="paged", scheduler="paged",
          kv_block_size=block, decode_kernel=kern)
      eng = ServeEngine(cfg, context_len=prompt_len + gen, max_batch=2,
                        prompt_capacity=prompt_len, params=params)
      params = eng.params
      eng.submit([1] * 8, max_new_tokens=2)      # absorb the compiles
      eng.run_to_completion()
      eng.reset_stats()
      handles = [eng.submit(p, max_new_tokens=m) for p, m in trace]
      with Stopwatch() as sw:
        eng.run_to_completion()
      toks[kern] = [h.tokens for h in handles]
      n_tok = sum(len(t) for t in toks[kern])
      lat = eng.stats.decode_latency()
      out["policies"][policy][kern] = {
          "tok_per_s": round(n_tok / max(sw.seconds, 1e-9), 2),
          "decode_step_p50_ms": lat["p50_ms"],
          "decode_step_p99_ms": lat["p99_ms"],
          "block_native": bool(eng.layout.block_native),
          "decode_traffic": eng.layout.decode_traffic,
      }
      print(f"decode[{policy}/{kern}]: {n_tok} tok in {sw.seconds:.2f}s, "
            f"step p50 {lat['p50_ms']} ms, "
            f"path {eng.layout.decode_traffic['decode_path']} "
            f"(dense materialized "
            f"{eng.layout.decode_traffic['dense_materialized_bytes_per_step']}"
            f" B/step)")
    out["policies"][policy]["tokens_identical"] = (
        toks["xla"] == toks["pallas-interpret"])
    if not out["policies"][policy]["tokens_identical"]:
      print(f"decode[{policy}]: TOKENS DIVERGED across decode kernels")
  native = out["policies"]["pq"]["pallas-interpret"]["decode_traffic"]
  out["pq_block_native_dense_bytes"] = (
      native["dense_materialized_bytes_per_step"])
  dense = out["policies"]["pq"]["xla"]["decode_traffic"]
  out["pq_dense_gather_bytes"] = dense["dense_materialized_bytes_per_step"]
  print(f"decode: paged pq dense-materialized bytes/step "
        f"{out['pq_dense_gather_bytes']} (xla) -> "
        f"{out['pq_block_native_dense_bytes']} (block-native)")
  return out


def run_workload(arch: str = "tinyllama-1.1b", n_requests: int = 12,
                 seed: int = 3, pcie_gbps: float = 0.002) -> dict:
  """Trace-driven serving under the virtual clock, per policy x arrival.

  Each cell runs the identical seeded trace twice — overlapped spill/fetch
  vs the serialized fallback — asserting bit-identical greedy tokens and
  recording the SLO view (TTFT/TPOT percentiles, goodput, queueing) plus
  the stall attribution both ways; `transfer_stall_ratio` < 1 is the
  overlap win.  The pool is sized so the trace forces spills (the same
  pressure the tiered tests apply) and the link is slowed to ~MB/s so
  transfer time is visible against the fixed decode-step budget — at the
  real 16 GB/s these reduced-config payloads drain in microseconds and
  every mode looks identical.  A final re-run of one cell checks
  end-to-end determinism (same seed -> same token streams)."""
  import dataclasses
  from repro.configs import get_arch
  from repro.launch import workload as wl
  from repro.launch.engine import ServeEngine

  # per-policy sizing: pq needs sink+recent headroom and longer requests to
  # pressure the pool (its streaming window retires blocks as it decodes)
  sizing = {
      "exact": dict(context_len=64, prompt_capacity=32, num_blocks=5,
                    host_blocks=24, prompt_len=(20, 30), gen=(10, 16)),
      "pq": dict(context_len=96, prompt_capacity=64, num_blocks=7,
                 host_blocks=32, prompt_len=(42, 58), gen=(12, 24)),
  }
  out = {"cache_layout": "tiered", "scheduler": "tiered", "batch": 2,
         "kv_block_size": 16, "n_requests": n_requests, "seed": seed,
         "pcie_gbps": pcie_gbps, "policies": {}}
  params_by_policy: dict = {}

  def one(policy: str, arrival: str, overlap: bool):
    sz = sizing[policy]
    cfg = dataclasses.replace(
        get_arch(arch, reduced=True), cache_policy=policy,
        dtype_str="bfloat16", cache_layout="tiered", scheduler="tiered",
        kv_block_size=16)
    eng = ServeEngine(cfg, context_len=sz["context_len"], max_batch=2,
                      prompt_capacity=sz["prompt_capacity"],
                      num_blocks=sz["num_blocks"],
                      host_blocks=sz["host_blocks"],
                      params=params_by_policy.get(policy),
                      clock=wl.VirtualClock(overlap=overlap))
    params_by_policy[policy] = eng.params
    eng.layout.ledger.pcie_gbps = pcie_gbps
    spec = wl.WorkloadSpec(
        arrival=arrival, rate=400.0, burstiness=6.0, n_requests=n_requests,
        seed=seed, tenants=(wl.TenantSpec(prompt_len=sz["prompt_len"],
                                          max_new_tokens=sz["gen"]),))
    return eng, wl.WorkloadDriver(eng, spec).run()

  for policy in ("pq", "exact"):
    out["policies"][policy] = {}
    for arrival in ("poisson", "bursty"):
      eng_o, res_o = one(policy, arrival, True)
      eng_s, res_s = one(policy, arrival, False)
      identical = res_o.token_streams == res_s.token_streams
      rep = res_o.report
      stall_o = rep["stall"]["transfer_stall_s"]
      stall_s = res_s.report["stall"]["transfer_stall_s"]
      out["policies"][policy][arrival] = {
          "tokens_identical": identical,
          "requests": rep["requests"],
          "goodput_frac": rep["goodput_frac"],
          "goodput_tok_s": rep["goodput_tok_s"],
          "deadline_met_frac": rep["deadline_met_frac"],
          "ttft_p50_s": rep["ttft"]["p50_s"],
          "ttft_p99_s": rep["ttft"]["p99_s"],
          "tpot_p50_s": rep["tpot"]["p50_s"],
          "tpot_p99_s": rep["tpot"]["p99_s"],
          "queue_p99_s": rep["queue"]["p99_s"],
          "spills": eng_o.stats.spills, "fetches": eng_o.stats.fetches,
          "prefetches": eng_o.stats.prefetches,
          "stall": rep["stall"],
          "stall_serialized": res_s.report["stall"],
          "transfer_stall_ratio": (round(stall_o / stall_s, 4)
                                   if stall_s else None),
      }
      print(f"workload[{policy}/{arrival}]: goodput "
            f"{100 * rep['goodput_frac']:.0f}%, ttft p99 "
            f"{rep['ttft']['p99_s']} s, transfer stall {stall_o:.4f} s "
            f"overlapped vs {stall_s:.4f} s serialized"
            f"{'' if identical else '  TOKENS DIVERGED'}")
  # end-to-end determinism: the same (spec, seed) cell twice -> identical
  # token streams and SLO report
  _, a = one("exact", "poisson", True)
  _, b = one("exact", "poisson", True)
  out["determinism_ok"] = (a.token_streams == b.token_streams
                           and a.report == b.report)
  print(f"workload: determinism_ok={out['determinism_ok']}")
  return out


# One mesh cell, run in a fresh interpreter: the bench process's jax is
# already initialized with a single CPU device, and
# --xla_force_host_platform_device_count only takes effect before the first
# jax import — so every cell (mesh=1 included, same numerics baseline) is a
# subprocess with the flag in its environment.  Prints one JSON line.
_MESH_PROBE = r'''
import dataclasses, json, sys
import jax
from repro.common.timing import Stopwatch
from repro.configs import get_arch
from repro.launch.engine import ServeEngine

arch, policy, mesh_model = sys.argv[1], sys.argv[2], int(sys.argv[3])
cfg = dataclasses.replace(
    get_arch(arch, reduced=True), cache_policy=policy, dtype_str="bfloat16",
    cache_layout="paged", scheduler="paged", kv_block_size=16,
    # every probed mesh size must divide the kv heads for heads-mode
    # identity; the reduced configs ship 2 kv heads, so widen to 4x4 (g=1)
    n_heads=4, n_kv_heads=4)
eng = ServeEngine(cfg, context_len=48, max_batch=2, prompt_capacity=32,
                  mesh_model=mesh_model)
eng.submit([1] * 8, max_new_tokens=2)          # absorb the compiles
eng.run_to_completion()
eng.reset_stats()
trace = [(list(range(3, 35 - 5 * i)), 12) for i in range(4)]
hs = [eng.submit(p, max_new_tokens=m) for p, m in trace]
with Stopwatch() as sw:
  eng.run_to_completion()
n_tok = sum(len(h.tokens) for h in hs)
mi = eng.mesh_info()
ps = mi.get("per_shard")
if ps is None:                                 # mesh=1: no plan, pool local
  total = sum(l.nbytes for l in jax.tree_util.tree_leaves(eng.layout.storage))
  ps = {"bytes_per_shard": total, "total_bytes": total}
print(json.dumps({
    "tok_per_s": round(n_tok / max(sw.seconds, 1e-9), 2),
    "tokens": [h.tokens for h in hs],
    "mode": mi["mode"],
    "bytes_per_shard": ps["bytes_per_shard"],
    "total_bytes": ps["total_bytes"],
}))
'''


def run_mesh(arch: str = "tinyllama-1.1b", sizes=(1, 2, 4)) -> dict:
  """Sharded-serving scaling: tok/s and per-shard pool bytes vs mesh size.

  Each (policy, mesh) cell replays the identical staggered trace through the
  paged engine on a forced 8-host-device CPU mesh (see `_MESH_PROBE` for why
  each cell is a subprocess) and the record asserts greedy-token identity
  against the mesh=1 cell.  On CPU the tok/s column measures overhead, not
  speedup — the scaling claim needs real devices; the byte column is the
  capacity-wall figure: heads-mode pool bytes per shard drop ~1/N.
  """
  # the probes run on forced CPU devices whatever this process runs on
  out = {"platform": "cpu", "devices_forced": 8,
         "cache_layout": "paged", "scheduler": "paged",
         "batch": 2, "prompt_len": 32, "gen": 12, "sizes": list(sizes),
         "policies": {}}
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env = dict(os.environ,
             XLA_FLAGS="--xla_force_host_platform_device_count=8",
             JAX_PLATFORMS="cpu")
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(root, "src")]
      + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
  for policy in ("pq", "exact"):
    cells = {}
    for m in sizes:
      proc = subprocess.run(
          [sys.executable, "-c", _MESH_PROBE, arch, policy, str(m)],
          env=env, capture_output=True, text=True, timeout=1200)
      if proc.returncode != 0:
        raise RuntimeError(
            f"mesh probe {policy}/mesh={m} failed:\n{proc.stderr[-2000:]}")
      cells[m] = json.loads(proc.stdout.strip().splitlines()[-1])
    ref = cells[sizes[0]]["tokens"]
    identical = all(cells[m]["tokens"] == ref for m in sizes)
    out["policies"][policy] = {
        "tokens_identical": identical,
        "mesh": {str(m): {k: cells[m][k] for k in
                          ("tok_per_s", "mode", "bytes_per_shard",
                           "total_bytes")} for m in sizes},
    }
    line = ", ".join(
        f"x{m}: {cells[m]['tok_per_s']} tok/s "
        f"{cells[m]['bytes_per_shard']} B/shard ({cells[m]['mode']})"
        for m in sizes)
    print(f"mesh[{policy}]: {line}"
          f"{'' if identical else '  TOKENS DIVERGED'}")
  return out


def run_packed_codecs(arch: str = "tinyllama-1.1b", prompt_len: int = 352,
                      gen: int = 48, block: int = 16, num_blocks: int = 46,
                      host_blocks: int = 192) -> dict:
  """Packed KV codec measurements (kernels/packing.py), two levels.

  Spill: the PR 3 forced-spill trace through the tiered engine with the
  exact policy under spill codec int8 vs q4/q8 — identical traffic, only
  the host-tier representation differs.  `q4_vs_int8_spill_bytes` is the
  headline: the sub-byte group layout (f16 scale/min per 32 values +
  nibble codes) roughly halves int8's per-row f32-header layout.

  Resident: a short decode trace with the exact policy, dense fp32 store
  vs q4 packed resident store, each under `xla` vs `pallas-interpret` and
  across {paged, tiered} — asserting greedy-token identity between the
  packed block-native kernel and the dequantizing XLA reference (they
  share one dequant formula), and recording the pool capacity ratio
  (`resident_q4_vs_fp32_bytes`, ~0.19 at head_dim 16).
  """
  import dataclasses
  from repro.configs import get_arch
  from repro.launch.engine import ServeEngine

  out = {"kv_block_size": block, "batch": 2, "prompt_len": prompt_len,
         "gen": gen, "spill": {}, "resident": {}}
  for codec in ("int8", "q4", "q8"):
    cfg = dataclasses.replace(
        get_arch(arch, reduced=True), cache_policy="exact",
        dtype_str="bfloat16", cache_layout="tiered", scheduler="tiered",
        kv_block_size=block, spill_codec=codec)
    eng = ServeEngine(cfg, context_len=prompt_len + gen, max_batch=2,
                      prompt_capacity=prompt_len, num_blocks=num_blocks,
                      host_blocks=host_blocks)
    for i in range(2):
      eng.submit([7 + i] * (prompt_len - 8 * i), max_new_tokens=gen)
    eng.run_to_completion()
    led = eng.layout.ledger
    out["spill"][codec] = {
        "spills": eng.stats.spills, "fetches": eng.stats.fetches,
        "spill_bytes": led.spill_bytes,
        "spill_raw_bytes": led.spill_raw_bytes,
        "fetch_bytes": led.fetch_bytes,
        "modeled_pcie_s": round(led.modeled_pcie_s, 6),
    }
    print(f"packed-spill[{codec}]: {eng.stats.spills} spills "
          f"({led.spill_bytes} B post-codec, {led.spill_raw_bytes} B raw)")
  int8_b = out["spill"]["int8"]["spill_bytes"]
  for codec in ("q4", "q8"):
    out[f"{codec}_vs_int8_spill_bytes"] = (
        round(out["spill"][codec]["spill_bytes"] / int8_b, 4)
        if int8_b else None)
  print(f"packed: q4 spill traffic = {out['q4_vs_int8_spill_bytes']} of "
        f"int8 (q8 = {out['q8_vs_int8_spill_bytes']})")

  trace = [(list(range(3, 3 + 32 - 4 * i)), 16) for i in range(4)]
  params = None
  for layout in ("paged", "tiered"):
    toks = {}
    cap = {}
    for codec in ("none", "q4"):
      for kern in ("xla", "pallas-interpret"):
        cfg = dataclasses.replace(
            get_arch(arch, reduced=True), cache_policy="exact",
            dtype_str="float32", cache_layout=layout, scheduler=layout,
            kv_block_size=block, decode_kernel=kern,
            kv_resident_codec=codec)
        eng = ServeEngine(cfg, context_len=48, max_batch=2,
                          prompt_capacity=32, params=params)
        params = eng.params
        hs = [eng.submit(p, max_new_tokens=m) for p, m in trace]
        eng.run_to_completion()
        toks[(codec, kern)] = [h.tokens for h in hs]
        cap[codec] = eng.kv_bytes()["capacity_bytes"]
    cell = {
        "tokens_identical_q4": (toks[("q4", "xla")]
                                == toks[("q4", "pallas-interpret")]),
        "tokens_identical_fp32": (toks[("none", "xla")]
                                  == toks[("none", "pallas-interpret")]),
        "capacity_bytes_fp32": cap["none"],
        "capacity_bytes_q4": cap["q4"],
    }
    out["resident"][layout] = cell
    print(f"packed-resident[{layout}]: pool {cap['none']} B fp32 -> "
          f"{cap['q4']} B q4; kernel==xla tokens "
          f"q4={cell['tokens_identical_q4']} "
          f"fp32={cell['tokens_identical_fp32']}")
  fp32_cap = out["resident"]["paged"]["capacity_bytes_fp32"]
  out["resident_q4_vs_fp32_bytes"] = (
      round(out["resident"]["paged"]["capacity_bytes_q4"] / fp32_cap, 4)
      if fp32_cap else None)
  print(f"packed: resident q4 pool = {out['resident_q4_vs_fp32_bytes']} "
        f"of fp32")
  return out


def run_recovery(arch: str = "tinyllama-1.1b", n_requests: int = 16,
                 seed: int = 3, pcie_gbps: float = 0.002) -> dict:
  """Fault-tolerant serving measurements (PR 9), three legs.

  Shedding: an overload trace (tight SLOs against the fixed virtual-clock
  decode budget, small device pool) through the SLO-enforcing engine
  (`--scheduler slo --slo-enforce`) vs the stalling baseline on the
  identical trace.  The headline is `shed_vs_stall_goodput`: shedding
  doomed requests early must *raise* goodput tok/s — the survivors make
  their deadlines instead of everyone missing together.

  Faults: the corrupt-spill plan (checksummed spill frames, recovery via
  recompute-prefill) over the forced-spill trace, asserting surviving
  requests' greedy tokens match the fault-free oracle bit for bit.

  Restore: a shared-prefix trace served, the prefix cache snapshotted
  (checkpoint/ckpt.py), and a *fresh* engine restored from it replaying
  the trace — warm prefix hit-tokens must beat the cold engine's, with
  bit-identical token streams.
  """
  import dataclasses
  import tempfile
  from repro.configs import get_arch
  from repro.launch import slo as slo_lib
  from repro.launch import workload as wl
  from repro.launch.engine import ServeEngine
  from repro.runtime.fault_tolerance import make_fault_plan

  sz = dict(context_len=64, prompt_capacity=32, num_blocks=5,
            host_blocks=24, prompt_len=(20, 30), gen=(10, 16))
  cfg = dataclasses.replace(
      get_arch(arch, reduced=True), cache_policy="exact",
      dtype_str="bfloat16", cache_layout="tiered", scheduler="tiered",
      kv_block_size=16)
  params_box: dict = {}

  def tiered(scheduler="tiered", **kw):
    c = dataclasses.replace(cfg, scheduler=scheduler)
    eng = ServeEngine(c, context_len=sz["context_len"], max_batch=2,
                      prompt_capacity=sz["prompt_capacity"],
                      num_blocks=sz["num_blocks"],
                      host_blocks=sz["host_blocks"],
                      params=params_box.get("p"),
                      clock=wl.VirtualClock(), **kw)
    params_box["p"] = eng.params
    eng.layout.ledger.pcie_gbps = pcie_gbps
    return eng

  out = {"cache_layout": "tiered", "batch": 2, "kv_block_size": 16,
         "n_requests": n_requests, "seed": seed, "pcie_gbps": pcie_gbps}

  # --- shedding vs stalling under overload -------------------------------
  tight = slo_lib.SLOSpec(ttft_s=0.02, tpot_s=0.002)
  tenant = wl.TenantSpec(prompt_len=sz["prompt_len"],
                         max_new_tokens=sz["gen"], slo=tight)
  over = wl.WorkloadSpec(arrival="poisson", rate=400.0, burstiness=6.0,
                         n_requests=n_requests, seed=seed, tenants=(tenant,))
  shed_eng = tiered(scheduler="slo", slo_enforce=True)
  r_shed = wl.WorkloadDriver(shed_eng, over).run()
  stall_eng = tiered()
  r_stall = wl.WorkloadDriver(stall_eng, over).run()
  out["shedding"] = {
      "scheduler": "slo",
      "shed_requests": shed_eng.stats.shed_requests,
      "degradation_state": shed_eng.stats.degradation_state,
      "degradation_transitions": len(shed_eng.stats.degradation_transitions),
      "goodput_tok_s": r_shed.report["goodput_tok_s"],
      "goodput_frac": r_shed.report["goodput_frac"],
      "goodput_tok_s_no_shedding": r_stall.report["goodput_tok_s"],
      "goodput_frac_no_shedding": r_stall.report["goodput_frac"],
      "shed_vs_stall_goodput": (
          round(r_shed.report["goodput_tok_s"]
                / r_stall.report["goodput_tok_s"], 4)
          if r_stall.report["goodput_tok_s"] else None),
  }
  print(f"recovery[shedding]: goodput {r_shed.report['goodput_tok_s']} "
        f"tok/s shedding ({shed_eng.stats.shed_requests} shed) vs "
        f"{r_stall.report['goodput_tok_s']} tok/s stalling")

  # --- corrupted spill pages: survivors bit-identical to the oracle ------
  base = wl.WorkloadSpec(
      arrival="poisson", rate=400.0, burstiness=6.0, n_requests=8,
      seed=seed, tenants=(wl.TenantSpec(prompt_len=sz["prompt_len"],
                                        max_new_tokens=sz["gen"]),))
  oracle_eng = tiered()
  r_oracle = wl.WorkloadDriver(oracle_eng, base).run()
  fault_eng = tiered(fault_injector=make_fault_plan(
      "corrupt-spill", 1.0, seed=seed, max_failures=2))
  r_fault = wl.WorkloadDriver(fault_eng, base).run()
  survivors_ok = all(
      toks == r_oracle.token_streams[i]
      for i, toks in r_fault.token_streams.items()
      if i not in r_fault.failed_indices)
  out["faults"] = {
      "kind": "corrupt-spill",
      "corrupt_pages": fault_eng.stats.corrupt_pages,
      "failed": len(r_fault.failed_indices),
      "survivor_tokens_identical": survivors_ok,
  }
  print(f"recovery[faults]: {fault_eng.stats.corrupt_pages} corrupt pages "
        f"recovered, survivors identical={survivors_ok}")

  # --- snapshot/restore: warm prefix hits after a restart ----------------
  def paged(snapshot_dir=None):
    c = dataclasses.replace(cfg, cache_layout="paged", scheduler="paged")
    return ServeEngine(c, context_len=sz["context_len"], max_batch=2,
                       prompt_capacity=sz["prompt_capacity"], num_blocks=10,
                       prefix_cache=True, params=params_box.get("p"),
                       clock=wl.VirtualClock(), snapshot_dir=snapshot_dir)

  shared = wl.WorkloadSpec(
      arrival="poisson", rate=200.0, n_requests=6, seed=seed + 2,
      tenants=(wl.TenantSpec(prompt_len=(20, 28), max_new_tokens=(6, 10),
                             shared_prefix_len=16),))
  with tempfile.TemporaryDirectory() as snap_dir:
    e1 = paged(snapshot_dir=snap_dir)
    wl.WorkloadDriver(e1, shared).run()
    e1.save_snapshot(step=1)
    warm = paged(snapshot_dir=snap_dir)
    r_warm = wl.WorkloadDriver(warm, shared).run()
    cold = paged()
    r_cold = wl.WorkloadDriver(cold, shared).run()
  tokens_ok = r_warm.token_streams == r_cold.token_streams
  out["restore"] = {
      "restored_prefix_blocks": warm.stats.restored_prefix_blocks,
      "warm_hit_tokens": warm.layout.prefix_index.hit_tokens,
      "cold_hit_tokens": cold.layout.prefix_index.hit_tokens,
      "tokens_identical": tokens_ok,
  }
  print(f"recovery[restore]: {warm.stats.restored_prefix_blocks} blocks "
        f"restored, hit tokens {cold.layout.prefix_index.hit_tokens} cold "
        f"-> {warm.layout.prefix_index.hit_tokens} warm, "
        f"tokens identical={tokens_ok}")

  # --- shard loss: host-mirror restore vs abort-and-recompute ------------
  out["shard"] = run_shard_recovery(arch, seed=seed)
  return out


# One shard-recovery cell in a fresh interpreter: like `_MESH_PROBE`, the
# 4-way mesh needs 8 forced host devices, which only takes effect before
# the first jax import.  A seeded shard-loss plan kills one shard mid-run;
# the cell serves the identical workload under one --shard-redundancy mode
# and prints its goodput + recovery counters as one JSON line.
_SHARD_PROBE = r'''
import dataclasses, json, sys
import jax
from repro.configs import get_arch
from repro.launch import workload as wl
from repro.launch.engine import ServeEngine
from repro.runtime import fault_tolerance as ft

arch, redundancy, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
assert len(jax.devices()) == 8, jax.devices()
cfg = dataclasses.replace(
    get_arch(arch, reduced=True), cache_policy="exact", dtype_str="bfloat16",
    cache_layout="tiered", scheduler="tiered", kv_block_size=16,
    # 4 kv heads so the 4-way mesh runs heads mode (a dead shard then voids
    # a kv-head slice of every block — the case redundancy exists for)
    n_heads=4, n_kv_heads=4)
plan = ft.make_fault_plan("shard-loss", 0.05, seed=seed, max_failures=1)
eng = ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                  num_blocks=5, host_blocks=24, mesh_model=4,
                  shard_redundancy=redundancy, fault_injector=plan,
                  clock=wl.VirtualClock())
spec = wl.WorkloadSpec(arrival="poisson", rate=400.0, burstiness=6.0,
                       n_requests=12, seed=seed,
                       tenants=(wl.TenantSpec(prompt_len=(20, 30),
                                              max_new_tokens=(10, 16)),))
r = wl.WorkloadDriver(eng, spec).run()
print(json.dumps({
    "goodput_tok_s": r.report["goodput_tok_s"],
    "goodput_frac": r.report["goodput_frac"],
    "served_tok_s": r.report["served_tok_s"],
    "losses": eng.stats.shard_losses,
    "replans": eng.stats.shard_replans,
    "mirror_restores": eng.stats.shard_mirror_restores,
    "recovered_requests": eng.stats.shard_recovered_requests,
    "preempts": eng.stats.preempts,
    "failed": len(r.failed_indices),
    "tokens": {str(k): list(v) for k, v in sorted(r.token_streams.items())},
}))
'''


def run_shard_recovery(arch: str = "tinyllama-1.1b", seed: int = 3) -> dict:
  """Shard-loss recovery (PR 10): `--shard-redundancy host-mirror` vs
  `none` on the identical seeded kill.

  Both cells replay the same workload on a 4-way heads mesh and lose the
  same shard at the same step; `none` recovers every resident request by
  abort-and-recompute (PR 9's recompute-prefill path), `host-mirror` by
  checksummed host-copy fetch + re-scatter under the replanned mesh.  The
  headline is `mirror_vs_recompute_goodput` > 1: restoring KV beats
  regenerating it.  Token streams must agree across the two modes (greedy
  decode: recovery changes *when* tokens appear, never *which*)."""
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env = dict(os.environ,
             XLA_FLAGS="--xla_force_host_platform_device_count=8",
             JAX_PLATFORMS="cpu")
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(root, "src")]
      + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
  cells = {}
  for redundancy in ("none", "host-mirror"):
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_PROBE, arch, redundancy, str(seed)],
        env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
      raise RuntimeError(
          f"shard recovery probe {redundancy} failed:\n{proc.stderr[-2000:]}")
    cells[redundancy] = json.loads(proc.stdout.strip().splitlines()[-1])
  none_c, mirror_c = cells["none"], cells["host-mirror"]
  tokens_ok = none_c["tokens"] == mirror_c["tokens"]
  out = {
      "platform": "cpu",       # forced CPU devices, like run_mesh's probes
      "devices_forced": 8, "mesh_model": 4, "mode": "heads", "seed": seed,
      "tokens_identical": tokens_ok,
      "none": {k: none_c[k] for k in
               ("goodput_tok_s", "goodput_frac", "served_tok_s", "losses",
                "replans", "mirror_restores", "recovered_requests",
                "preempts", "failed")},
      "host_mirror": {k: mirror_c[k] for k in
                      ("goodput_tok_s", "goodput_frac", "served_tok_s",
                       "losses", "replans", "mirror_restores",
                       "recovered_requests", "preempts", "failed")},
      "mirror_vs_recompute_goodput": (
          round(mirror_c["goodput_tok_s"] / none_c["goodput_tok_s"], 4)
          if none_c["goodput_tok_s"] else None),
  }
  print(f"recovery[shard]: goodput {mirror_c['goodput_tok_s']} tok/s "
        f"host-mirror ({mirror_c['mirror_restores']} restores) vs "
        f"{none_c['goodput_tok_s']} tok/s recompute "
        f"({none_c['preempts']} preempts), tokens identical={tokens_ok}")
  return out


#: --json keeps this many newest run records; the trajectory file was
#: growing ~400 lines per PR unbounded.  Legacy records (including a
#: pre-trajectory single-record file, migrated by _load_history) are
#: preserved until they age past the window.
BENCH_HISTORY_KEEP = 50


def run_serve_json(out_path: str, arch: str = "tinyllama-1.1b",
                   batch: int = 2, prompt_len: int = 64, gen: int = 16) -> int:
  from repro.launch.serve import ServeRun

  record = {"timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "git_sha": _git_sha(), "arch": arch, "reduced": True,
            "batch": batch, "prompt_len": prompt_len, "gen": gen,
            # the timed loop decodes fixed-batch over contiguous slabs; the
            # tiered section below carries the pooled-layout axes
            "cache_layout": "contiguous", "scheduler": "fixed-batch",
            "kv_block_size": 0, "policies": {}}
  for policy in ("pq", "exact"):
    run = ServeRun(arch=arch, reduced=True, batch=batch,
                   prompt_len=prompt_len, gen=gen, cache_policy=policy)
    res = run.run()
    record["policies"][policy] = {
        "tok_per_s": round(res["tok_per_s"], 2),
        "prefill_s": round(res["prefill_s"], 4),
        "decode_s": round(res["decode_s"], 4),
        "decode_step_p50_ms": res["decode_step_p50_ms"],
        "decode_step_p99_ms": res["decode_step_p99_ms"],
        "decode_kernel": res["decode_kernel"],
    }
    print(f"serve[{policy}]: {res['tok_per_s']:.1f} tok/s "
          f"(prefill {res['prefill_s']:.2f}s, decode {res['decode_s']:.2f}s, "
          f"step p50 {res['decode_step_p50_ms']:.2f} / p99 "
          f"{res['decode_step_p99_ms']:.2f} ms)")
  from repro.configs import get_arch
  if get_arch(arch, reduced=True).family in ("dense", "moe"):
    record["tiered"] = run_tiered_transfer(arch)
  else:
    # ServeEngine (and therefore the tiered trace) rejects recurrent/modal
    # families; keep the timed record instead of dying on the extra section
    record["tiered"] = None
    print(f"tiered: skipped ({arch} family not engine-servable)")
  if get_arch(arch, reduced=True).family == "dense":
    record["prefix"] = run_prefix_trace(arch)
  else:
    # chain sharing needs causal per-position prefill (dense family)
    record["prefix"] = None
    print(f"prefix: skipped ({arch} family has no chunked suffix prefill)")
  if get_arch(arch, reduced=True).family in ("dense", "moe"):
    record["decode_kernels"] = run_decode_kernels(arch)
  else:
    record["decode_kernels"] = None
    print(f"decode kernels: skipped ({arch} family not engine-servable)")
  if get_arch(arch, reduced=True).family in ("dense", "moe"):
    record["workload"] = run_workload(arch)
  else:
    record["workload"] = None
    print(f"workload: skipped ({arch} family not engine-servable)")
  if get_arch(arch, reduced=True).family in ("dense", "moe"):
    record["mesh"] = run_mesh(arch)
  else:
    record["mesh"] = None
    print(f"mesh: skipped ({arch} family not engine-servable)")
  if get_arch(arch, reduced=True).family in ("dense", "moe"):
    record["packed"] = run_packed_codecs(arch)
  else:
    record["packed"] = None
    print(f"packed codecs: skipped ({arch} family not engine-servable)")
  if get_arch(arch, reduced=True).family == "dense":
    record["recovery"] = run_recovery(arch)
  else:
    # the restore leg needs the prefix cache's chunked suffix prefill
    record["recovery"] = None
    print(f"recovery: skipped ({arch} family has no prefix cache)")
  history = _load_history(out_path)
  history.append(record)
  dropped = len(history) - BENCH_HISTORY_KEEP
  if dropped > 0:
    history = history[-BENCH_HISTORY_KEEP:]
    print(f"pruned {dropped} oldest run record(s); keeping the newest "
          f"{BENCH_HISTORY_KEEP}")
  from repro.launch.serve import write_json_atomic
  write_json_atomic(out_path, {"runs": history})
  print(f"appended run {len(history)} to {out_path}")
  return 0


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--json", action="store_true",
                  help="run the serve benchmark and append a timestamped "
                       "record to the JSON trajectory")
  ap.add_argument("--out", default="BENCH_serve.json",
                  help="output path for --json mode")
  ap.add_argument("--arch", default="tinyllama-1.1b")
  ap.add_argument("--batch", type=int, default=2)
  ap.add_argument("--prompt-len", type=int, default=64)
  ap.add_argument("--gen", type=int, default=16)
  args = ap.parse_args()
  compile_cache.enable()
  if args.json:
    sys.exit(run_serve_json(args.out, arch=args.arch, batch=args.batch,
                            prompt_len=args.prompt_len, gen=args.gen))
  sys.exit(run_csv())


if __name__ == '__main__':
  main()
