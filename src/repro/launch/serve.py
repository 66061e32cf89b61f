"""End-to-end serving driver: batched prefill -> cache policy -> decode loop.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
      --prompt-len 128 --gen 32 --batch 4 --cache-policy pq

The KV-cache method is selected by registry key (`--cache-policy`): `exact`,
`pq` (AQPIM, default), `skvq`, `snapkv`, `streamingllm`, `pqcache` — the
paper's Fig. 10 sweep surface.  With `pq` this exercises the full AQPIM
inference path (paper Fig. 3a): prefill computes exact attention AND builds
the compressed cache (importance-weighted windowed clustering, hidden behind
prefill); the decode loop appends tokens by PQ-encoding ring-buffer
evictions and attends directly on compressed data.

`--engine` runs the same architecture through the continuous-batching
`ServeEngine` instead: staggered prompt lengths admitted into one batch,
finishing at different steps.  Engine storage and admission are pluggable:
`--cache-layout {contiguous,paged,tiered}` picks the physical KV layout
(paged = fixed-size token blocks from a shared pool,
`--kv-block-size`/`--num-blocks`; tiered adds a host spill tier,
`--host-blocks`/`--spill-codec`) and `--scheduler {fifo,sjf,paged,tiered}`
the admission policy (`paged` preempts-and-recomputes on pool exhaustion;
`tiered` spills the LRU-coldest request's KV to the host tier instead and
fetches it back later).  Per-run occupancy/waste/preempt/spill counters
print from `engine.stats`; `--stats-json PATH` dumps them machine-readably
(plus `layout_bytes` and the tier-boundary `transfer` ledger) so CI and
benches can assert on them.

`--workload N` switches to the trace-driven harness (launch/workload.py):
N seeded requests arrive over a virtual clock (`--arrival poisson|bursty|
trace`, `--arrival-rate`, `--burstiness`, `--trace-file`), each carrying an
SLO (`--slo-ttft`/`--slo-tpot`); host-tier transfers overlap decode through
the double-buffered fetch stage (or serialize with `--no-overlap` — same
greedy tokens either way), `--fetch-fail-rate` injects host-tier fetch
faults the engine must survive, and the run reports TTFT/TPOT percentiles,
goodput, and compute/transfer/idle stall attribution instead of wall-clock
throughput.  Deterministic end to end: two runs with one seed produce
identical token streams and reports.

Robustness knobs (PR 9): `--slo-enforce` turns per-request deadlines into
admission control — doomed queued work is shed, the degradation
state machine (NORMAL -> PRESSURED -> SHEDDING) records in the stats —
and pairs with `--scheduler slo` (priority-then-EDF).  `--fault-kind
{fetch,corrupt-spill,alloc-exhaustion,decode-transient} --fault-rate P`
injects seeded faults on one surface through a `FaultPlan`
(runtime/fault_tolerance.py); the engine must recover without leaking
blocks or corrupting survivors' tokens.  `--snapshot-dir DIR` restores the
prefix cache from the latest snapshot at startup (crash-safe warm
restart) and `--save-snapshot` persists it after the run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.common import compile_cache
from repro.common.timing import Stopwatch, latency_percentiles_ms
from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.core import cache_registry, decode_dispatch, tiers
from repro.kernels import packing
from repro.launch import scheduler as scheduler_lib
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_local_mesh
from repro.parallel import sharding as shd
from repro.runtime import fault_tolerance as ft


@dataclasses.dataclass
class ServeRun:
  arch: str
  reduced: bool = True
  batch: int = 4
  prompt_len: int = 128
  gen: int = 32
  cache_policy: str = "pq"
  decode_kernel: str = "auto"      # core/decode_dispatch registry key
  measure_latency: bool = True     # run the extra synced decode pass for
                                   # p50/p99 (costs ~one more prefill+decode;
                                   # tests that only want tokens turn it off)
  pq: bool = True                  # legacy knob: False downgrades the default
                                   # "pq" policy to "exact" (no effect on other
                                   # explicitly chosen policies)
  warmup: bool = True              # compile outside the timed sections
  seed: int = 0
  greedy: bool = True
  mesh: Any = None

  def run(self):
    cfg = get_arch(self.arch, reduced=self.reduced)
    cfg = dataclasses.replace(cfg, cache_policy=self.cache_policy,
                              decode_kernel=self.decode_kernel)
    if not self.pq:
      cfg = dataclasses.replace(cfg, pq_enabled=False)
    context = self.prompt_len + self.gen
    mesh = self.mesh or make_local_mesh()
    shape = ShapeConfig("serve", context, self.batch, "decode")
    progs = steps_lib.build_programs(cfg, shape, mesh, donate=False)
    model = progs.model

    key = jax.random.PRNGKey(self.seed)
    params = jax.jit(
        model.init,
        out_shardings=shd.make_shardings(progs.param_specs, mesh))(key)
    prompts = jax.random.randint(
        key, (self.batch, self.prompt_len), 0, cfg.vocab_size)
    modal = None
    if cfg.frontend == "audio_frames":
      modal = jnp.zeros((self.batch, context, cfg.d_model), cfg.dtype)
    elif cfg.frontend == "vision_patches":
      modal = jnp.zeros((self.batch, cfg.n_modal_tokens, cfg.d_model),
                        cfg.dtype)

    with mesh:
      prefill = jax.jit(model.prefill)
      m_pref = modal[:, :self.prompt_len] if (
          modal is not None and cfg.frontend == "audio_frames") else modal
      step = jax.jit(model.decode_step)
      if self.warmup:
        # trace+compile outside the stopwatches so timings measure execution
        logits_w, cache_w = prefill(params, prompts, m_pref)
        jax.block_until_ready(step(
            params, jnp.argmax(logits_w, -1).astype(jnp.int32), cache_w,
            jnp.full((self.batch,), self.prompt_len, jnp.int32),
            modal[:, :1] if modal is not None
            and cfg.frontend == "audio_frames" else modal))

      with Stopwatch() as sw_prefill:
        logits, cache = prefill(params, prompts, m_pref)
        sw_prefill.wait_for(logits)

      def step_inputs(i):
        """Per-step (lengths, modal slice) — ONE definition, so the timed
        throughput loop and the latency pass drive the identical program."""
        lengths = jnp.full((self.batch,), self.prompt_len + i, jnp.int32)
        m_step = (modal[:, self.prompt_len + i:self.prompt_len + i + 1]
                  if modal is not None and cfg.frontend == "audio_frames"
                  else modal)
        return lengths, m_step

      tokens = [jnp.argmax(logits, -1).astype(jnp.int32)]
      with Stopwatch() as sw_decode:
        for i in range(self.gen):
          lengths, m_step = step_inputs(i)
          logits, cache = step(params, tokens[-1], cache, lengths, m_step)
          tokens.append(jnp.argmax(logits, -1).astype(jnp.int32))
        sw_decode.wait_for(tokens[-1])

      # per-step latency distribution: a second decode pass with a sync per
      # step, so the throughput loop above keeps its async dispatch overlap
      # while p50/p99 measure real launch->result step times.  Opt-out for
      # callers that only want tokens (it costs another prefill+decode).
      step_s = []
      if self.measure_latency:
        import time as _time
        logits_l, cache_l = prefill(params, prompts, m_pref)
        tok_l = jnp.argmax(logits_l, -1).astype(jnp.int32)
        for i in range(self.gen):
          lengths, m_step = step_inputs(i)
          t0 = _time.perf_counter()
          logits_l, cache_l = step(params, tok_l, cache_l, lengths, m_step)
          tok_l = jnp.argmax(logits_l, -1).astype(jnp.int32)
          jax.block_until_ready(tok_l)
          step_s.append(_time.perf_counter() - t0)

    lat = latency_percentiles_ms(step_s)
    out = jnp.stack(tokens[:-1], axis=1)
    policy_name = cfg.resolved_cache_policy() if not cfg.attn_free else "none"
    # record what actually ran, not the request: 'auto' resolves per
    # backend, and a policy without a kernel implementation runs xla
    # whatever was asked for
    kernel_key = (model.cache_policy.effective_decode_kernel
                  if model.cache_policy is not None else "xla")
    return {
        "tokens": out,
        "prefill_s": sw_prefill.seconds,
        "decode_s": sw_decode.seconds,
        "tok_per_s": self.batch * self.gen / max(sw_decode.seconds, 1e-9),
        "decode_step_p50_ms": lat["p50_ms"],
        "decode_step_p99_ms": lat["p99_ms"],
        "cache_policy": policy_name,
        "decode_kernel": kernel_key,
        "pq": policy_name == "pq",
    }


def build_engine(args, clock=None, fault_injector=None):
  """Construct the ServeEngine exactly as the CLI flags describe it (kept
  separate so tests can assert every flag reaches the engine/config).
  `clock`/`fault_injector` are the workload harness's virtual clock and
  fetch-fault injector (None for the wall-clock demo paths)."""
  from repro.launch.engine import ServeEngine
  cfg = get_arch(args.arch, reduced=args.reduced)
  # host_blocks passes through as-is: an explicit --host-blocks 0 (no host
  # tier, recompute fallback only) is distinct from None (layout default)
  cfg = dataclasses.replace(cfg, cache_policy=args.cache_policy,
                            cache_layout=args.cache_layout,
                            scheduler=args.scheduler,
                            kv_block_size=args.kv_block_size,
                            host_blocks=args.host_blocks,
                            spill_codec=args.spill_codec,
                            kv_resident_codec=args.kv_resident_codec,
                            prefix_cache=args.prefix_cache,
                            prefix_cache_blocks=args.prefix_cache_blocks,
                            decode_kernel=args.decode_kernel)
  context = args.prompt_len + args.gen
  engine = ServeEngine(cfg, context_len=context, max_batch=args.batch,
                       prompt_capacity=args.prompt_len,
                       num_blocks=args.num_blocks, clock=clock,
                       fault_injector=fault_injector,
                       mesh_model=getattr(args, "mesh_model", None),
                       slo_enforce=getattr(args, "slo_enforce", False),
                       snapshot_dir=getattr(args, "snapshot_dir", None),
                       shard_redundancy=getattr(args, "shard_redundancy",
                                                "none"))
  if getattr(args, "pcie_gbps", None):
    ledger = getattr(engine.layout, "ledger", None)
    if ledger is not None:
      ledger.pcie_gbps = args.pcie_gbps
  return engine


def dump_stats_json(engine, path: str, extra: Any = None) -> None:
  """Machine-readable run record: EngineStats.as_dict() + the layout's true
  footprint + (tiered) the tier-boundary transfer ledger.  `extra` merges
  additional top-level sections (the workload harness adds its SLO report
  under the "workload" key)."""
  payload = engine.stats.as_dict()
  if extra:
    payload.update(extra)
  payload["layout"] = engine.layout.name
  payload["scheduler"] = engine.scheduler.name
  payload["decode_kernel"] = (
      engine.model.cache_policy.effective_decode_kernel
      if engine.model.cache_policy is not None else "xla")
  payload["layout_bytes"] = engine.layout.bytes(
      active_slots=engine.active_count)
  payload["kv_bytes"] = engine.kv_bytes()
  if hasattr(engine.layout, "decode_traffic"):
    payload["decode_traffic"] = engine.layout.decode_traffic
  ledger = getattr(engine.layout, "ledger", None)
  if ledger is not None:
    payload["transfer"] = ledger.as_dict()
  payload["mesh"] = engine.mesh_info()
  if hasattr(engine, "shard_health_info"):
    payload["shard_health"] = engine.shard_health_info()
  index = getattr(engine.layout, "prefix_index", None)
  if index is not None:
    payload["prefix_cache"] = dict(
        budget_blocks=index.budget_blocks, held_blocks=index.held_blocks,
        chain_nodes=index.chain_nodes, full_entries=index.full_entries,
        hits=index.hits, full_hits=index.full_hits,
        hit_tokens=index.hit_tokens, evicted_blocks=index.evicted_blocks)
  write_json_atomic(path, payload)


def write_json_atomic(path: str, payload: Any) -> None:
  """Write JSON via a sibling temp file + `os.replace`, so a crash (or a
  concurrent reader — CI tails these files) never observes a torn record:
  the path either holds the previous complete document or the new one."""
  tmp = f"{path}.tmp.{os.getpid()}"
  with open(tmp, "w") as f:
    json.dump(payload, f, indent=2)
    f.write("\n")
    f.flush()
    os.fsync(f.fileno())
  os.replace(tmp, path)


def run_engine_demo(args) -> None:
  """Continuous batching: mixed prompt lengths, staggered finishes."""
  engine = build_engine(args)
  cfg = engine.cfg
  context = args.prompt_len + args.gen
  key = jax.random.PRNGKey(0)
  # drain one throwaway request so the three jit compiles land outside the
  # timed section (same reason ServeRun has warmup) — it must ask for >= 2
  # tokens, else it finishes at admission and never compiles the decode step
  warm_len = min(8, args.prompt_len, max(1, context - 2))
  engine.submit([1] * warm_len, max_new_tokens=min(2, context - warm_len))
  engine.run_to_completion()
  # the warmup drain just paid the trace+compile cost; drop its samples so
  # the printed/dumped decode-latency percentiles are steady-state steps
  engine.reset_stats()
  floor = min(8, args.prompt_len)
  rng_lens = [max(floor, args.prompt_len - 17 * i)
              for i in range(args.batch + 2)]
  max_new = max(1, min(args.gen, max(2, args.gen // 2)))
  for i, ln in enumerate(rng_lens):
    prompt = jax.random.randint(jax.random.fold_in(key, i), (ln,), 0,
                                cfg.vocab_size)
    engine.submit(list(map(int, prompt)), max_new_tokens=max_new)
  with Stopwatch() as sw:
    done = engine.run_to_completion()
  n_tok = sum(len(r.tokens) for r in done)
  kernel_key = (engine.model.cache_policy.effective_decode_kernel
                if engine.model.cache_policy is not None else "xla")
  print(f"engine: {len(done)} requests, {n_tok} tokens in {sw.seconds:.2f}s "
        f"({n_tok / max(sw.seconds, 1e-9):.1f} tok/s) "
        f"[layout={args.cache_layout} scheduler={args.scheduler} "
        f"kernel={kernel_key}"
        f"{' block-native' if getattr(engine.layout, 'block_native', False) else ''}]")
  if hasattr(engine.layout, "decode_traffic"):
    tm = engine.layout.decode_traffic
    print(f"decode traffic (peak/step): {tm['decode_path']} — dense "
          f"materialized {tm['dense_materialized_bytes_per_step']} B, "
          f"block reads {tm['block_read_bytes_per_step']} B, row writes "
          f"{tm['row_write_bytes_per_step']} B")
  if engine.shard_plan is not None and engine.shard_plan.active:
    mi = engine.mesh_info()
    ps = mi.get("per_shard", {})
    print(f"mesh: {mi['shards']}-way over '{mi['axis']}' ({mi['mode']} "
          f"mode, bit_identical={mi['bit_identical']}), "
          f"{ps.get('bytes_per_shard', 0)} B pool/shard of "
          f"{ps.get('total_bytes', 0)} B total")
  print(f"engine stats: {engine.stats.summary()}")
  by = engine.layout.bytes(active_slots=engine.active_count)
  if by["kind"] in ("paged", "tiered"):
    print(f"kv memory: peak {by['peak_blocks']}/{by['num_blocks']} blocks "
          f"x {by['block_bytes']} B (+{by['resident_bytes_per_slot']} B/slot "
          f"resident), pool capacity {by['capacity_bytes']} B")
    if by["kind"] == "tiered":
      print(f"host tier: {by['host_allocated_blocks']}/{by['host_blocks']} "
            f"blocks holding {by['spilled_requests']} spilled requests "
            f"({by['spilled_payload_bytes']} B)")
      print(f"transfer: {engine.layout.ledger.summary()}")
    if args.prefix_cache:
      idx = engine.layout.prefix_index
      print(f"prefix cache: {idx.held_blocks}/{idx.budget_blocks} blocks "
            f"held ({idx.chain_nodes} chain nodes, {idx.full_entries} full "
            f"entries), {idx.hits} hits ({idx.hit_tokens} tokens), "
            f"{by['forked_blocks']} cow-forks, {by['dedup_bytes']} B "
            f"deduped now")
  else:
    print(f"kv memory: {by['total_bytes']} B contiguous "
          f"({by['per_slot_bytes']} B/slot x {args.batch} slots)")
  for r in done:
    print(f"  rid={r.rid} prompt_len={r.prompt_len} admitted@{r.admitted_step}"
          f" finished@{r.finished_step} preempts={r.preempt_count} "
          f"spills={r.spill_count} tokens={r.tokens[:8]}")
  if args.stats_json:
    dump_stats_json(engine, args.stats_json)
    print(f"stats written to {args.stats_json}")


def workload_spec_from_args(args):
  """Translate the --workload/--arrival/--slo-* flag family into a
  `WorkloadSpec` (kept separate so tests can assert the plumbing)."""
  from repro.launch import slo as slo_lib
  from repro.launch import workload as workload_lib
  slo = slo_lib.SLOSpec(ttft_s=args.slo_ttft, tpot_s=args.slo_tpot)
  p_lo = max(1, args.prompt_len // 2)
  g_lo = max(1, args.gen // 2)
  tenants = []
  for i in range(max(1, args.tenants)):
    # tenant 0 of a multi-tenant mix shares a prompt prefix (the traffic
    # pattern the prefix cache exists for) and carries priority 1, so the
    # SLO scheduler protects it when overload forces shedding
    shared = p_lo // 2 if (i == 0 and args.tenants > 1) else 0
    prio = 1 if (i == 0 and args.tenants > 1) else 0
    tenants.append(workload_lib.TenantSpec(
        name=f"t{i}", prompt_len=(p_lo, args.prompt_len),
        max_new_tokens=(g_lo, args.gen), shared_prefix_len=shared, slo=slo,
        priority=prio))
  return workload_lib.WorkloadSpec(
      arrival=args.arrival, rate=args.arrival_rate,
      burstiness=args.burstiness, n_requests=args.workload,
      seed=args.workload_seed, tenants=tuple(tenants),
      trace_path=args.trace_file, fetch_fail_rate=args.fetch_fail_rate,
      fetch_fail_seed=args.workload_seed)


def run_workload_demo(args) -> None:
  """Trace-driven serving under the virtual clock: seeded arrivals feed the
  engine, transfers overlap decode (or serialize with --no-overlap), and
  the run reports SLO metrics instead of wall-clock throughput."""
  import warnings

  from repro.launch import slo as slo_lib
  from repro.launch import workload as workload_lib
  from repro.runtime.fault_tolerance import FaultPlan
  from repro.runtime.fault_tolerance import FetchFaultInjector
  from repro.runtime.fault_tolerance import make_fault_plan
  spec = workload_spec_from_args(args)
  clock = workload_lib.VirtualClock(overlap=not args.no_overlap)
  injector = None
  if getattr(args, "fault_kind", None):
    if spec.fetch_fail_rate > 0:
      raise SystemExit("--fault-kind conflicts with --fetch-fail-rate "
                       "(pick one injection surface spec)")
    injector = make_fault_plan(args.fault_kind, args.fault_rate,
                               seed=spec.fetch_fail_seed)
  elif spec.fetch_fail_rate > 0:
    warnings.warn(
        "--fetch-fail-rate is deprecated; use --fault-kind fetch "
        "--fault-rate R (the seeded multi-surface FaultPlan path)",
        DeprecationWarning, stacklevel=2)
    injector = FetchFaultInjector(fail_rate=spec.fetch_fail_rate,
                                  seed=spec.fetch_fail_seed)
  loss_rate = getattr(args, "shard_fault_loss", 0.0) or 0.0
  stall_rate = getattr(args, "shard_fault_stall", 0.0) or 0.0
  if loss_rate > 0 or stall_rate > 0:
    if injector is None:
      injector = FaultPlan(seed=spec.fetch_fail_seed)
    elif not isinstance(injector, FaultPlan):
      raise SystemExit(
          "--shard-fault-* needs the FaultPlan surfaces; replace "
          "--fetch-fail-rate with --fault-kind fetch --fault-rate")
    injector.shard_loss_rate = loss_rate
    injector.shard_stall_rate = stall_rate
  engine = build_engine(args, clock=clock, fault_injector=injector)
  driver = workload_lib.WorkloadDriver(engine, spec)
  result = driver.run()
  mode = "serialized" if args.no_overlap else "overlapped"
  print(f"workload: {spec.arrival} arrivals at {spec.rate}/s, "
        f"{len(driver.requests)} requests, {mode} spill/fetch "
        f"[layout={args.cache_layout} scheduler={args.scheduler} "
        f"policy={args.cache_policy}]")
  print(f"slo: {slo_lib.summary(result.report)}")
  print(f"engine stats: {engine.stats.summary()}")
  if getattr(args, "slo_enforce", False):
    print(f"admission control: {engine.stats.shed_requests} shed, "
          f"final state {engine.stats.degradation_state}, "
          f"{len(engine.stats.degradation_transitions)} transitions")
  if injector is not None and hasattr(injector, "by_surface"):
    print(f"fault plan: {injector.injected} injected {dict(injector.by_surface)}")
  if engine.stats.shard_losses or engine.stats.shard_stalls:
    print(f"shard health: {engine.shard_health_info()}")
  if getattr(args, "save_snapshot", False):
    saved = engine.save_snapshot(step=engine.stats.steps)
    if saved:
      print(f"prefix snapshot saved to {saved}")
    else:
      print("prefix snapshot skipped (needs --snapshot-dir + --prefix-cache)")
  if args.stats_json:
    dump_stats_json(engine, args.stats_json,
                    extra={"workload": dict(
                        result.report, arrival=spec.arrival, rate=spec.rate,
                        seed=spec.seed, overlap=not args.no_overlap)})
    print(f"stats written to {args.stats_json}")


def make_parser() -> argparse.ArgumentParser:
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--arch", default="tinyllama-1.1b")
  ap.add_argument("--reduced", action="store_true")
  ap.add_argument("--batch", type=int, default=4)
  ap.add_argument("--prompt-len", type=int, default=128)
  ap.add_argument("--gen", type=int, default=32)
  ap.add_argument("--cache-policy", default="pq",
                  choices=cache_registry.names())
  ap.add_argument("--cache-layout", default="contiguous",
                  choices=cache_registry.layout_names(),
                  help="physical KV storage (engine mode): contiguous slabs, "
                       "paged token blocks, or tiered (device + host pools "
                       "with compressed spill/fetch)")
  ap.add_argument("--scheduler", default="fifo",
                  choices=scheduler_lib.names(),
                  help="engine admission policy (paged requires "
                       "--cache-layout paged/tiered; tiered requires "
                       "--cache-layout tiered)")
  ap.add_argument("--decode-kernel", default="auto",
                  choices=decode_dispatch.names(),
                  help="decode attention implementation: xla (pure-JAX "
                       "reference), pallas (Mosaic kernels, TPU only), "
                       "pallas-interpret (kernels via the interpreter, runs "
                       "anywhere), auto (pallas on TPU, xla elsewhere).  "
                       "With paged/tiered layouts a pallas dispatch decodes "
                       "block-table-native: no dense gather/scatter round "
                       "trip")
  ap.add_argument("--kv-block-size", type=int, default=16,
                  help="paged-layout token-block granularity")
  ap.add_argument("--num-blocks", type=int, default=None,
                  help="paged-layout device pool size (default: batch * "
                       "capacity/block, i.e. contiguous-equivalent)")
  ap.add_argument("--host-blocks", type=int, default=None,
                  help="tiered-layout host (tier 1) pool size in blocks "
                       "(default: 4x the device pool)")
  # choices come from the registries so an unknown key fails at argparse
  # with the valid set listed, not layers later at CacheSpec validation
  ap.add_argument("--spill-codec", default="raw",
                  choices=tuple(sorted(tiers.SPILL_CODECS)),
                  help="tiered-layout exact-KV spill codec (core.tiers "
                       "registry; q4/q8 are GGUF-style packed groups); PQ "
                       "code rows always spill verbatim (they are the "
                       "compressed form)")
  ap.add_argument("--kv-resident-codec", default="none",
                  choices=tuple(packing.RESIDENT_CODECS),
                  help="exact-policy resident KV store: none keeps dense "
                       "floats; q4/q8 store sub-byte packed pages "
                       "(kernels/packing.py) decoded in-kernel — ~0.19x "
                       "the fp32 footprint at q4")
  ap.add_argument("--prefix-cache", action="store_true",
                  help="share prompt-prefix KV blocks across requests "
                       "(copy-on-write block tables + suffix-only prefill; "
                       "requires --cache-layout paged/tiered, token-exact "
                       "under greedy decoding)")
  ap.add_argument("--prefix-cache-blocks", type=int, default=None,
                  help="device blocks the prefix index may pin "
                       "(refcount+LRU budget; default: half the pool)")
  ap.add_argument("--mesh-model", type=int, default=None, metavar="N",
                  help="shard the engine decode over an N-way mesh model "
                  "axis (kv heads when divisible, else split-K over the "
                  "sequence for the exact policy); pooled layouts only. "
                  "N must divide the device count — on CPU force devices "
                  "with XLA_FLAGS=--xla_force_host_platform_device_count")
  ap.add_argument("--stats-json", default=None, metavar="PATH",
                  help="engine mode: dump EngineStats.as_dict() + layout "
                       "footprint + transfer ledger as JSON")
  ap.add_argument("--no-pq", action="store_true",
                  help="legacy alias for --cache-policy exact")
  ap.add_argument("--engine", action="store_true",
                  help="run the continuous-batching ServeEngine demo")
  # ---- workload harness (trace-driven traffic under a virtual clock) ----
  ap.add_argument("--workload", type=int, default=None, metavar="N",
                  help="drive the engine with N seeded trace-generated "
                       "requests under a virtual clock (implies --engine); "
                       "reports TTFT/TPOT/goodput SLO metrics")
  ap.add_argument("--arrival", default="poisson",
                  choices=("poisson", "bursty", "trace"),
                  help="arrival process: poisson (exponential gaps), bursty "
                       "(Gamma gaps, cv^2=--burstiness), or trace (replay "
                       "--trace-file)")
  ap.add_argument("--arrival-rate", type=float, default=50.0,
                  help="mean arrivals per virtual second")
  ap.add_argument("--burstiness", type=float, default=4.0,
                  help="cv^2 of bursty interarrivals (1 = Poisson)")
  ap.add_argument("--trace-file", default=None, metavar="PATH",
                  help="JSON arrival trace for --arrival trace")
  ap.add_argument("--slo-ttft", type=float, default=0.5,
                  help="SLO: time-to-first-token budget (virtual seconds)")
  ap.add_argument("--slo-tpot", type=float, default=0.05,
                  help="SLO: per-output-token budget (virtual seconds)")
  ap.add_argument("--workload-seed", type=int, default=0,
                  help="seed for the workload trace and fault injection "
                       "(same seed = identical trace, byte for byte)")
  ap.add_argument("--tenants", type=int, default=1,
                  help="synthetic tenant count; tenant 0 of a multi-tenant "
                       "mix shares a prompt prefix")
  ap.add_argument("--no-overlap", action="store_true",
                  help="serialized spill/fetch fallback: every transfer "
                       "stalls the virtual clock (tokens must stay "
                       "bit-identical to overlapped mode)")
  ap.add_argument("--fetch-fail-rate", type=float, default=0.0,
                  help="inject host-tier fetch faults at this per-attempt "
                       "probability (engine retries with bounded backoff)")
  ap.add_argument("--slo-enforce", action="store_true",
                  help="enforce per-request deadlines as admission control: "
                       "shed doomed queued/expired work, run the NORMAL -> "
                       "PRESSURED -> SHEDDING degradation state machine "
                       "(pairs with --scheduler slo)")
  ap.add_argument("--fault-kind", default=None,
                  choices=tuple(ft.FAULT_KINDS),
                  help="seeded multi-surface fault injection (FaultPlan): "
                       "fetch failures, corrupted spill pages (checksum-"
                       "detected, recovered by recompute-prefill), allocator "
                       "exhaustion spikes, transient decode-step failures "
                       "(bounded retry/backoff), or mesh shard loss/stall "
                       "(watchdog-confirmed, degraded-mesh replan)")
  ap.add_argument("--fault-rate", type=float, default=0.1,
                  help="per-event probability for --fault-kind (seeded by "
                       "--workload-seed)")
  ap.add_argument("--shard-fault-loss", type=float, default=0.0,
                  metavar="RATE",
                  help="per-step probability of a seeded shard-loss fault "
                       "(kills one mesh shard; the watchdog confirms the "
                       "death and the engine replans the survivors).  "
                       "Composes with --fault-kind")
  ap.add_argument("--shard-fault-stall", type=float, default=0.0,
                  metavar="RATE",
                  help="per-step probability of a seeded shard-stall fault "
                       "(one shard misses its decode heartbeat; sustained "
                       "stalls escalate to a confirmed death)")
  ap.add_argument("--shard-redundancy", default="none",
                  choices=("none", "host-mirror"),
                  help="KV redundancy against shard loss: host-mirror keeps "
                       "a checksummed host-tier copy of every resident "
                       "request's pool pages (written through the spill "
                       "codecs) so a dead shard's blocks restore by fetch + "
                       "re-scatter; none falls back to recompute-prefill")
  ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                  help="crash-safe prefix-cache snapshots: restore the "
                       "latest snapshot in DIR at engine startup (warm "
                       "prefix hits after a restart; requires "
                       "--prefix-cache)")
  ap.add_argument("--save-snapshot", action="store_true",
                  help="persist the prefix cache to --snapshot-dir after "
                       "the workload run")
  ap.add_argument("--pcie-gbps", type=float, default=None,
                  help="override the modeled tier-boundary link bandwidth "
                       "(smaller = transfers dominate, stressing overlap)")
  return ap


def main():
  ap = make_parser()
  args = ap.parse_args()
  compile_cache.enable()
  # --no-pq is an alias for --cache-policy exact; refuse a conflicting mix
  # rather than silently measuring the wrong policy
  if args.no_pq:
    if args.cache_policy not in ("pq", "exact"):
      ap.error(f"--no-pq conflicts with --cache-policy {args.cache_policy}")
    args.cache_policy = "exact"
  if args.workload is not None:
    args.engine = True               # the harness drives the engine
  if args.stats_json and not args.engine:
    ap.error("--stats-json requires --engine (EngineStats are engine-mode)")
  if args.arrival == "trace" and args.workload is not None \
      and not args.trace_file:
    ap.error("--arrival trace requires --trace-file")
  if args.save_snapshot and not args.snapshot_dir:
    ap.error("--save-snapshot requires --snapshot-dir")
  if args.fault_kind and args.workload is None:
    ap.error("--fault-kind requires --workload (fault plans drive the "
             "virtual-clock harness)")
  if (args.shard_fault_loss or args.shard_fault_stall) \
      and args.workload is None:
    ap.error("--shard-fault-* requires --workload (shard faults drive the "
             "virtual-clock harness)")

  if args.workload is not None:
    run_workload_demo(args)
    return
  if args.engine:
    run_engine_demo(args)
    return

  run = ServeRun(arch=args.arch, reduced=args.reduced, batch=args.batch,
                 prompt_len=args.prompt_len, gen=args.gen,
                 cache_policy=args.cache_policy,
                 decode_kernel=args.decode_kernel)
  res = run.run()
  print(f"arch={args.arch} policy={res['cache_policy']} "
        f"kernel={res['decode_kernel']} "
        f"prefill={res['prefill_s']:.2f}s decode={res['decode_s']:.2f}s "
        f"({res['tok_per_s']:.1f} tok/s, step p50 "
        f"{res['decode_step_p50_ms']:.2f} / p99 "
        f"{res['decode_step_p99_ms']:.2f} ms)")
  print("sample tokens:", res["tokens"][0, :16].tolist())


if __name__ == "__main__":
  main()
