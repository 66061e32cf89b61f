"""Continuous-batching serve engine over CacheLayout storage + a Scheduler.

The fixed-batch demo loop in `launch.serve` decodes B requests in lockstep:
all prompts share one length and all finish together.  Real serving (the
ROADMAP north star; LoL-PIM/PIMphony-style long-context PIM serving) needs
*continuous batching*: a request queue, slot-based admit/finish between
jitted decode steps, and per-slot length tracking.  That is what this
module provides:

    engine = ServeEngine(cfg, context_len=256, max_batch=4)
    h1 = engine.submit([12, 7, 99, ...], max_new_tokens=16)
    h2 = engine.submit(prompt2, max_new_tokens=4)       # any prompt length
    while engine.has_work:
      for done in engine.step():
        print(done.rid, done.tokens)
    print(engine.stats.summary())

Storage and policy are split along the PR 2 API boundary:

- *What* is cached is the `CachePolicy` codec (`cfg.cache_policy`: exact,
  AQPIM pq, skvq, ...).
- *Where* it lives is the `CacheLayout` (`cfg.cache_layout` /
  `cache_layout=` kwarg): `contiguous` capacity-sized slabs per slot,
  `paged` fixed-size token blocks from a shared `BlockAllocator` pool, or
  `tiered` — paged storage over a two-tier refcounted pool (device + host)
  with compressed spill/fetch through the policy's spill codecs.
- *Who runs next* is the `Scheduler` (`cfg.scheduler` / `scheduler=`):
  `fifo`, `sjf`, `paged` (admit-on-available-blocks, preempt-and-requeue
  on pool exhaustion — recompute preemption: a preempted request is re-
  prefilled from its prompt and, under greedy decoding, regenerates the
  identical tokens), `tiered` (swap preemption: the LRU-coldest victim's
  KV spills to the host tier and a later fetch resumes it mid-decode — no
  recompute; `engine.stats` counts spills/fetches, the bytes that crossed,
  and the PCIe time they model), or `prefix` (longest-cached-prefix-first
  cache-affinity admission).
- *What is already known* is the prefix cache (`cfg.prefix_cache` /
  `prefix_cache=`; pooled layouts only): admission looks the prompt up in
  the layout's `PrefixIndex` and either restores a whole-prompt snapshot
  (zero prefill — the first greedy token was published with it), shares
  the matched block chain copy-on-write and prefills **only the uncached
  suffix** through a fixed-shape chunked-prefill jit, or falls back to the
  ordinary full prefill.  All three paths produce bit-identical greedy
  tokens; `engine.stats` counts hits, hit tokens, cow-forks, and deduped
  bytes.

Mechanics
---------
- One jitted batch=1 prefill (prompts right-padded to `prompt_capacity`)
  plus the layout's own compiled programs (slot-insert and decode for
  contiguous; admit-scatter and gather->decode->scatter for paged) — a
  fixed number of compiles regardless of how many requests stream through.
- Per-request `lengths` thread through the CachePolicy API so requests at
  different positions coexist in one decode step.
- Greedy sampling.  Inactive slots still burn a decode lane; `engine.stats`
  now counts that waste (occupancy, wasted slot-steps, admits/preempts)
  instead of letting it pass silently.

Families with sequence-recurrent prefill state (ssm/hybrid) or extra modal
streams (vlm/audio) are not admitted — right-padded prefill would corrupt
their recurrent state.  Dense and MoE architectures are supported.
"""
from __future__ import annotations

import collections
import dataclasses
import warnings
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as ckpt_lib
from repro.common import timing
from repro.configs.base import ModelConfig
from repro.core import cache_registry
from repro.core import tiers as tiersmod
from repro.launch import mesh as mesh_lib
from repro.launch import scheduler as scheduler_lib
from repro.models import Model
from repro.parallel import serve_sharding as ssh
from repro.runtime import fault_tolerance


@dataclasses.dataclass
class RequestHandle:
  """One submitted generation request; `tokens` fills in as it decodes."""
  rid: int
  prompt: np.ndarray                 # (prompt_len,) int32
  max_new_tokens: int
  tokens: List[int] = dataclasses.field(default_factory=list)
  done: bool = False
  slot: Optional[int] = None
  admitted_step: Optional[int] = None
  finished_step: Optional[int] = None
  preempt_count: int = 0             # recompute preemptions (KV discarded)
  spilled: bool = False              # KV currently on the host tier
  spill_count: int = 0               # swap-outs (KV preserved across them)
  resume_len: int = 0                # cached length at swap-out
  resume_cur: int = 0                # pending token at swap-out
  # fault-tolerance (host-tier fetch faults, workload-harness injectable)
  fetch_failures: int = 0            # transient fetch faults survived so far
  failed: bool = False               # dropped after bounded fetch retries
  # SLO admission control (PR 9): deadline from SLOSpec, tenant priority,
  # and whether the engine shed this request instead of finishing it
  deadline_s: Optional[float] = None
  tenant: str = "default"
  priority: int = 0                  # higher sheds later under pressure
  shed: bool = False                 # cancelled by SLO/pressure shedding
  # virtual-clock timestamps (None on wall-clock engines); the workload
  # harness folds these into per-request TTFT/TPOT/queueing SLO metrics
  submitted_step: Optional[int] = None
  submit_s: Optional[float] = None   # arrival (stamped by the driver)
  admit_s: Optional[float] = None    # first admission (queueing delay ends)
  first_token_s: Optional[float] = None
  finish_s: Optional[float] = None

  @property
  def prompt_len(self) -> int:
    return int(self.prompt.shape[0])


@dataclasses.dataclass
class EngineStats:
  """Per-run engine counters (the wasted-compute blind spot, quantified)."""
  max_batch: int
  # mesh-sharded serving (PR 7): shard count and partition mode of the run's
  # ShardPlan ("none" | "heads" | "seq"); 1/"none" on single-device engines
  mesh_shards: int = 1
  mesh_mode: str = "none"
  steps: int = 0                 # step() calls, including idle ones
  decode_steps: int = 0          # batched decode launches
  busy_slot_steps: int = 0       # slot-steps that advanced a live request
  wasted_slot_steps: int = 0     # slot-steps that decoded garbage (idle lane)
  admits: int = 0
  preempts: int = 0              # recompute preemptions (tokens regenerated)
  finished: int = 0
  blocks_reclaimed: int = 0      # ring-reuse frees (paged streaming window)
  # tiered-layout spill/fetch accounting (zero on single-tier layouts)
  spills: int = 0                # swap-outs to the host tier (KV preserved)
  fetches: int = 0               # swap-ins from the host tier
  prefetches: int = 0            # fetch-ahead transfers started early
  spill_bytes: int = 0           # device -> host, post-spill-codec
  fetch_bytes: int = 0           # host -> device, post-spill-codec
  modeled_pcie_s: float = 0.0    # time that traffic would occupy the link
  fetch_failures: int = 0        # injected/transient fetch faults (requeued)
  fetch_aborts: int = 0          # IN_FLIGHT transfers rolled back to SPILLED
  failed_requests: int = 0       # dropped after exhausting bounded retries
  # multi-surface fault injection + SLO shedding (PR 9)
  shed_requests: int = 0         # cancelled by deadline/pressure shedding
  pressure_sheds: int = 0        # sheds triggered by pool exhaustion
  alloc_spikes: int = 0          # transient allocator-exhaustion injections
  decode_faults: int = 0         # transient decode-step faults retried
  corrupt_pages: int = 0         # corrupted spill pages detected + recovered
  restored_prefix_blocks: int = 0  # prefix blocks revived from a snapshot
  # shard fault tolerance (PR 10): watchdog + degraded-mesh replan counters
  shard_losses: int = 0          # shards confirmed dead by the watchdog
  shard_stalls: int = 0          # one-round shard straggles injected
  shard_replans: int = 0         # degraded-mesh re-plans adopted
  shard_mirror_restores: int = 0   # slots rebuilt from the host mirror
  shard_recovered_requests: int = 0  # requests recovered (mirror or recompute)
  dead_shards: List[int] = dataclasses.field(default_factory=list)
  shard_heartbeats: List[int] = dataclasses.field(default_factory=list)
  # graceful-degradation state machine: current state plus the transition
  # log (bounded; each entry records step/virtual time/old/new)
  degradation_state: str = "NORMAL"
  degradation_transitions: List[dict] = dataclasses.field(
      default_factory=list)
  # virtual-clock accounting (zero on wall-clock engines): where the run's
  # simulated makespan went — the stall-attribution split the SLO report
  # and the workload benchmark records break out
  virtual_s: float = 0.0         # simulated makespan so far
  compute_s: float = 0.0         # decode + prefill virtual time
  transfer_stall_s: float = 0.0  # blocked on the modeled PCIe link
  idle_s: float = 0.0            # no work due (waiting on arrivals)
  link_busy_s: float = 0.0       # link occupancy (overlapped or stalled)
  # prefix-cache accounting (zero when --prefix-cache is off)
  prefix_hits: int = 0           # admissions that matched the prefix index
  prefix_full_hits: int = 0      # whole-prompt hits (prefill skipped)
  prefix_hit_tokens: int = 0     # prompt tokens served from cached blocks
  prefill_tokens: int = 0        # prompt tokens actually prefilled (computed)
  forked_blocks: int = 0         # copy-on-write forks of shared blocks
  dedup_bytes: int = 0           # peak bytes saved by multi-mapped blocks
  # wall-clock per batched decode step (launch -> next-token sync: the
  # `engine.decode` span), the distribution CI's p99 regression guard
  # watches.  Bounded: a long-lived engine keeps the most recent window of
  # samples, not its whole history
  decode_step_s: collections.deque = dataclasses.field(
      default_factory=lambda: collections.deque(maxlen=4096), repr=False)
  # queue gauges the workload harness reads: depth sampled once per step(),
  # and per-request waiting time (submit -> first admit) in engine steps.
  # Same bounded-window policy as decode_step_s
  queue_depth_samples: collections.deque = dataclasses.field(
      default_factory=lambda: collections.deque(maxlen=4096), repr=False)
  queue_wait_steps: collections.deque = dataclasses.field(
      default_factory=lambda: collections.deque(maxlen=4096), repr=False)

  @property
  def occupancy(self) -> float:
    """Fraction of decode lanes that did useful work."""
    lanes = self.decode_steps * self.max_batch
    return self.busy_slot_steps / lanes if lanes else 0.0

  @property
  def prefix_hit_rate(self) -> float:
    """Fraction of submitted prompt tokens served from the prefix cache."""
    total = self.prefix_hit_tokens + self.prefill_tokens
    return self.prefix_hit_tokens / total if total else 0.0

  def decode_latency(self) -> dict:
    """Per-step decode latency percentiles (ms) over this run.

    Samples are raw wall clock: a cold step that traced+compiled is counted
    as-is.  Callers that want steady-state numbers drain a warmup request
    first and then reset the stats (`engine.stats = EngineStats(...)`) —
    the serve CLI demo and the benchmark harness both do."""
    return timing.latency_percentiles_ms(self.decode_step_s)

  def queue_gauges(self) -> dict:
    """Queue-pressure snapshot over the sample windows: current/mean/max
    depth and mean/max per-request waiting time (in engine steps)."""
    depth = list(self.queue_depth_samples)
    wait = list(self.queue_wait_steps)
    return dict(
        depth_now=int(depth[-1]) if depth else 0,
        depth_mean=round(float(np.mean(depth)), 3) if depth else 0.0,
        depth_max=int(max(depth)) if depth else 0,
        wait_steps_mean=round(float(np.mean(wait)), 3) if wait else 0.0,
        wait_steps_max=int(max(wait)) if wait else 0,
        depth_samples=len(depth), wait_samples=len(wait))

  def as_dict(self) -> dict:
    """Read-only snapshot: a fresh dict every call, counters untouched.
    Deque-valued fields (raw sample windows) are excluded *by type*, not by
    name — new gauges stay in-process automatically instead of leaking
    unserializable deques into stats-json (the old name-based filter only
    knew about decode_step_s)."""
    d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)
         if not isinstance(getattr(self, f.name), collections.deque)}
    d["occupancy"] = round(self.occupancy, 4)
    d["prefix_hit_rate"] = round(self.prefix_hit_rate, 4)
    d["decode_latency"] = self.decode_latency()
    d["queue"] = self.queue_gauges()
    return d

  def summary(self) -> str:
    s = (f"occupancy {100 * self.occupancy:.1f}% "
         f"({self.busy_slot_steps}/{self.decode_steps * self.max_batch} "
         f"slot-steps, {self.wasted_slot_steps} wasted) | "
         f"admits {self.admits}, preempts {self.preempts}, "
         f"finished {self.finished}, reclaimed {self.blocks_reclaimed} "
         f"blocks")
    lat = self.decode_latency()
    if lat["steps"]:
      s += (f" | decode step p50 {lat['p50_ms']:.2f} ms / "
            f"p99 {lat['p99_ms']:.2f} ms")
    if self.spills or self.fetches:
      s += (f" | spills {self.spills} ({self.spill_bytes} B), fetches "
            f"{self.fetches} ({self.fetch_bytes} B, {self.prefetches} "
            f"ahead), ~{self.modeled_pcie_s * 1e3:.2f} ms PCIe")
    if self.prefix_hits:
      s += (f" | prefix hits {self.prefix_hits} ({self.prefix_full_hits} "
            f"full), {100 * self.prefix_hit_rate:.1f}% of prompt tokens "
            f"cached, {self.forked_blocks} cow-forks, {self.dedup_bytes} B "
            f"deduped")
    if self.fetch_failures or self.failed_requests:
      s += (f" | fetch faults {self.fetch_failures} "
            f"({self.fetch_aborts} aborts, {self.failed_requests} requests "
            f"dropped)")
    if self.shed_requests or self.degradation_transitions:
      s += (f" | shed {self.shed_requests} ({self.pressure_sheds} under "
            f"pressure), degradation {self.degradation_state} "
            f"({len(self.degradation_transitions)} transitions)")
    if self.decode_faults or self.corrupt_pages or self.alloc_spikes:
      s += (f" | faults: {self.decode_faults} decode retried, "
            f"{self.corrupt_pages} corrupt pages recovered, "
            f"{self.alloc_spikes} alloc spikes")
    if self.restored_prefix_blocks:
      s += f" | restored {self.restored_prefix_blocks} prefix blocks"
    if self.shard_losses or self.shard_stalls:
      s += (f" | shard faults: {self.shard_losses} lost "
            f"(dead {self.dead_shards}), {self.shard_stalls} stalled, "
            f"{self.shard_replans} replans, "
            f"{self.shard_mirror_restores} mirror restores, "
            f"{self.shard_recovered_requests} requests recovered")
    if self.virtual_s:
      s += (f" | virtual {self.virtual_s:.3f} s "
            f"({1e3 * self.compute_s:.1f} ms compute, "
            f"{1e3 * self.transfer_stall_s:.1f} ms transfer stall, "
            f"{1e3 * self.idle_s:.1f} ms idle)")
    if self.mesh_shards > 1:
      s += f" | mesh {self.mesh_shards}-way ({self.mesh_mode})"
    return s


#: Graceful-degradation states, escalation order.
DEGRADATION_STATES = ("NORMAL", "PRESSURED", "SHEDDING")


class DegradationController:
  """NORMAL -> PRESSURED -> SHEDDING state machine over pool pressure.

  Observes free-block fraction and queue depth once per engine step and
  moves one state at a time, each direction gated by a sustain count — a
  single tight step cannot flip the engine into shedding, and one lucky
  step cannot flip it back (hysteresis).  What each state *does* lives in
  the engine: PRESSURED progressively evicts prefix-cache entries and
  stops admitting already-expired work; SHEDDING additionally cancels
  queued requests that provably cannot meet their deadline.
  """
  PRESSURE_FREE_FRAC = 0.25    # escalate NORMAL -> PRESSURED below this
  SHED_FREE_FRAC = 0.10        # escalate PRESSURED -> SHEDDING below this
  SUSTAIN = 2                  # consecutive observations to move one state

  def __init__(self):
    self.state = "NORMAL"
    self._up = 0
    self._down = 0

  def observe(self, free_frac: float,
              queue_depth: int) -> Optional[Tuple[str, str]]:
    """Feed one step's pressure reading; returns (old, new) on transition."""
    if free_frac <= self.SHED_FREE_FRAC and queue_depth > 0:
      want = 2
    elif free_frac <= self.PRESSURE_FREE_FRAC:
      want = 1
    else:
      want = 0
    cur = DEGRADATION_STATES.index(self.state)
    if want > cur:
      self._up, self._down = self._up + 1, 0
      if self._up >= self.SUSTAIN:
        old, self.state = self.state, DEGRADATION_STATES[cur + 1]
        self._up = 0
        return (old, self.state)
    elif want < cur:
      self._down, self._up = self._down + 1, 0
      if self._down >= self.SUSTAIN:
        old, self.state = self.state, DEGRADATION_STATES[cur - 1]
        self._down = 0
        return (old, self.state)
    else:
      self._up = self._down = 0
    return None


class ServeEngine:
  """Slot-based continuous batching over `Model.prefill` / `Model.decode_step`."""

  def __init__(self, cfg: ModelConfig, *, context_len: int = 256,
               max_batch: int = 4, prompt_capacity: Optional[int] = None,
               params: Any = None, seed: int = 0,
               cache_layout: Optional[str] = None,
               scheduler: Optional[str] = None,
               block_size: Optional[int] = None,
               num_blocks: Optional[int] = None,
               host_blocks: Optional[int] = None,
               prefix_cache: Optional[bool] = None,
               prefix_cache_blocks: Optional[int] = None,
               clock: Any = None,
               fault_injector: Any = None,
               max_fetch_retries: int = 3,
               max_decode_retries: int = 3,
               slo_enforce: bool = False,
               snapshot_dir: Optional[str] = None,
               mesh: Any = None,
               mesh_model: Optional[int] = None,
               shard_redundancy: str = "none",
               shard_confirm_after: int = 2):
    if cfg.family not in ("dense", "moe"):
      raise ValueError(
          f"ServeEngine supports dense/moe attention families, got "
          f"{cfg.family!r} (recurrent prefill state cannot be right-padded)")
    if cfg.frontend != "none":
      raise ValueError("ServeEngine does not manage modal input streams")
    self.cfg = cfg
    self.context_len = context_len
    self.max_batch = max_batch
    self.prompt_capacity = prompt_capacity or max(context_len // 2,
                                                  cfg.pq_sink + cfg.pq_recent)
    if not self.prompt_capacity < context_len:
      raise ValueError(
          f"prompt_capacity {self.prompt_capacity} must be < context_len "
          f"{context_len}")
    if (cfg.resolved_cache_policy() == "pq"
        and self.prompt_capacity < cfg.pq_sink + cfg.pq_recent):
      raise ValueError(
          f"pq policy needs prompt_capacity >= sink+recent "
          f"({cfg.pq_sink}+{cfg.pq_recent}), got {self.prompt_capacity}")

    layout_name = cache_layout or cfg.cache_layout
    sched_name = scheduler or cfg.scheduler
    self.scheduler = scheduler_lib.make(sched_name)
    layout_cls = cache_registry.get_layout(layout_name)
    if self.scheduler.preemptive and not layout_cls.pooled:
      raise ValueError(
          f"scheduler {sched_name!r} gates admission on the block pool; "
          f"it requires cache_layout='paged' or 'tiered', got "
          f"{layout_name!r}")
    if self.scheduler.spills and not layout_cls.spills:
      raise ValueError(
          f"scheduler {sched_name!r} spills victims to the host tier; "
          f"it requires cache_layout='tiered', got {layout_name!r}")

    # mesh-sharded serving (PR 7): resolve the partition plan before any
    # storage is built so placement, dispatch resolution, and the decode
    # shard_map all see the same frozen decision
    if mesh is None and mesh_model is not None and mesh_model > 1:
      mesh = mesh_lib.make_local_mesh(model=mesh_model)
    self.shard_plan = None if mesh is None else ssh.plan_for(cfg, mesh)
    plan_active = self.shard_plan is not None and self.shard_plan.active
    if plan_active and not layout_cls.pooled:
      raise ValueError(
          f"sharded serving (mesh model axis "
          f"{self.shard_plan.size}) partitions the block pool; it requires "
          f"cache_layout='paged' or 'tiered', got {layout_name!r} — pass "
          f"--cache-layout paged/tiered, or drop --mesh-model to 1 (and "
          f"--shard-redundancy to none) to serve unsharded")

    self.model = Model(cfg, context_len=context_len)
    if params is None:
      params = jax.jit(self.model.init)(jax.random.PRNGKey(seed))
    if plan_active:
      # the network outside attention is replicated — commit params to every
      # mesh device once instead of letting GSPMD re-broadcast per program
      params = ssh.replicate(params, self.shard_plan)
    self.params = params

    def prefill(p, t, ln):
      return self.model.prefill(p, t, None, lengths=ln)
    self._prefill = jax.jit(prefill)
    # physical cache storage + its compiled admit/decode programs
    self.prefix_cache = (cfg.prefix_cache if prefix_cache is None
                         else bool(prefix_cache))
    self.layout = cache_registry.make_layout(
        layout_name, self.model, max_batch,
        block_size=block_size, num_blocks=num_blocks,
        host_blocks=host_blocks if host_blocks is not None
        else cfg.host_blocks,
        prefix_cache=self.prefix_cache,
        prefix_cache_blocks=prefix_cache_blocks
        if prefix_cache_blocks is not None else cfg.prefix_cache_blocks,
        shard_plan=self.shard_plan,
        shard_redundancy=shard_redundancy)
    if self.prefix_cache:
      # the chunked suffix prefill must attend over exactly the padded
      # extent the full prefill uses — that is the bit-exactness contract
      self.layout.set_prompt_capacity(self.prompt_capacity)
      self._prefix_chunk = self.layout.block

    # virtual-clock serving (workload harness): compute and host-tier
    # transfers consume simulated time; overlap mode lets IN_FLIGHT
    # transfers drain while resident requests decode.  clock=None is the
    # wall-clock engine, bit-identical to the pre-harness behavior.
    self.clock = clock
    self.fault_injector = fault_injector
    self.max_fetch_retries = max_fetch_retries
    self.max_decode_retries = max_decode_retries
    #: rid -> virtual completion time of its in-flight host->device fetch
    self._transfer_ready: dict = {}

    # SLO enforcement + graceful degradation (PR 9): opt-in — with
    # slo_enforce=False the engine is bit-identical to the pre-PR9 loop
    self.slo_enforce = bool(slo_enforce)
    self._degradation = DegradationController()
    self.snapshot_dir = snapshot_dir

    # shard fault tolerance (PR 10): per-shard decode heartbeat watchdog.
    # Runs on unsharded engines too (shards=1): a confirmed "shard 0" death
    # there is a whole-pool loss and every resident request is recovered.
    self.shard_health = ssh.ShardHealth(
        self.shard_plan.size if plan_active else 1,
        confirm_after=shard_confirm_after)

    self.stats = self._new_stats()
    self._lengths = np.zeros((max_batch,), np.int32)
    self._cur = np.zeros((max_batch,), np.int32)
    self._slots: List[Optional[RequestHandle]] = [None] * max_batch
    self._queue: collections.deque = collections.deque()
    self._next_rid = 0
    self._step_no = 0

    # crash-safe restart: revive the prefix cache from the latest snapshot
    # so the restarted engine serves warm prefix hits instead of cold ones
    if self.snapshot_dir and self.prefix_cache:
      latest = ckpt_lib.latest_step(self.snapshot_dir)
      if latest is not None:
        try:
          tree, extra = ckpt_lib.load_raw(self.snapshot_dir, latest)
        except ckpt_lib.CheckpointCorruption as exc:
          # refuse the snapshot loudly, pool untouched: a cold prefix cache
          # is correct (just slower); a bit-rotted one decodes garbage
          warnings.warn(
              f"prefix-cache snapshot step {latest} in {self.snapshot_dir} "
              f"refused, starting cold: {exc}", RuntimeWarning,
              stacklevel=2)
        else:
          self.stats.restored_prefix_blocks = self.layout.prefix_restore(
              tree, extra)

  # -------------------------------------------------------------------------
  # public API
  # -------------------------------------------------------------------------

  def _new_stats(self) -> EngineStats:
    plan = self.shard_plan
    return EngineStats(
        max_batch=self.max_batch,
        mesh_shards=plan.size if plan is not None else 1,
        mesh_mode=plan.mode if plan is not None else "none")

  def kv_bytes(self) -> dict:
    """Stats-json `kv_bytes` section: the codecs shaping KV storage plus
    what the layout's live arrays actually occupy — the packed-codec
    capacity claim measured on allocated buffers, not modeled."""
    info = dict(spill_codec=self.cfg.spill_codec,
                kv_resident_codec=self.cfg.kv_resident_codec)
    if hasattr(self.layout, "bytes"):
      info.update(self.layout.bytes(active_slots=self.active_count))
    return info

  def mesh_info(self) -> dict:
    """Stats-json `mesh` section: the resolved plan plus what each shard
    actually holds (pool bytes split sharded/replicated)."""
    plan = self.shard_plan
    if plan is None:
      return dict(axis=ssh.MODEL_AXIS, mode="none", shards=1,
                  devices=[str(jax.devices()[0])], bit_identical=True)
    info = plan.describe()
    if hasattr(self.layout, "storage"):
      info["per_shard"] = ssh.per_shard_bytes(plan, self.layout.storage)
    return info

  def reset_stats(self) -> None:
    """Fresh counters (e.g. after a warmup drain so latency percentiles
    measure steady-state steps).  Fields mirroring the layout's cumulative
    ledger (spill/fetch bytes, modeled PCIe time, forked blocks) are
    re-synced immediately and stay cumulative over the engine's life —
    event *counts* restart at zero."""
    self.stats = self._new_stats()
    self._sync_transfer_stats()
    self._sync_prefix_stats()

  def submit(self, prompt: Sequence[int], max_new_tokens: int = 16, *,
             deadline_s: Optional[float] = None, tenant: str = "default",
             priority: int = 0) -> RequestHandle:
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    if not 0 < prompt.shape[0] <= self.prompt_capacity:
      raise ValueError(
          f"prompt length {prompt.shape[0]} not in (0, {self.prompt_capacity}]")
    if max_new_tokens < 1:
      raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if prompt.shape[0] + max_new_tokens > self.context_len:
      raise ValueError("prompt + max_new_tokens exceeds context capacity")
    if not self.layout.fits(prompt.shape[0] + max_new_tokens,
                            prompt.shape[0]):
      raise ValueError(
          f"request needs more KV blocks than the whole pool holds "
          f"({self.layout!r}); raise num_blocks or shorten the request")
    req = RequestHandle(rid=self._next_rid, prompt=prompt,
                        max_new_tokens=max_new_tokens,
                        submitted_step=self._step_no,
                        deadline_s=deadline_s, tenant=tenant,
                        priority=priority)
    if self.clock is not None and req.submit_s is None:
      req.submit_s = self.clock.now
    self._next_rid += 1
    self._queue.append(req)
    return req

  @property
  def has_work(self) -> bool:
    return bool(self._queue) or any(r is not None for r in self._slots)

  @property
  def active_count(self) -> int:
    return sum(r is not None for r in self._slots)

  @property
  def active_requests(self) -> List[Tuple[int, RequestHandle]]:
    """(slot, request) pairs currently decoding — scheduler's read view."""
    return [(s, r) for s, r in enumerate(self._slots) if r is not None]

  @property
  def queue_view(self) -> Tuple[RequestHandle, ...]:
    """Waiting requests in queue order — scheduler's read view."""
    return tuple(self._queue)

  def admissible(self, req: RequestHandle) -> bool:
    """Can this queued request be admitted right now?  Prefix-cache aware:
    a request whose prompt prefix is cached needs only its unshared suffix
    blocks, which `can_admit` alone would overestimate.  Schedulers gate on
    this instead of reaching into the layout."""
    total = req.prompt_len + req.max_new_tokens
    if req.spilled:
      return (self.layout.can_fetch(req.rid, total)
              and self._transfer_ready_ok(req.rid))
    if self.prefix_cache:
      plan = self.layout.prefix_plan(req.prompt, total)
      return plan["need"] <= self.layout.free_blocks
    return self.layout.can_admit(req.prompt_len, total)

  @property
  def fetch_depth(self) -> int:
    """How many host->device fetches may be materializing at once: 1 on a
    wall-clock engine (the PR 3 one-step hint), 2 under an overlapping
    virtual clock (double-buffered: one transfer finalizing while the next
    drains behind it), 0 in serialized-fallback mode (every transfer is
    charged at the admit that needs it — the bit-identity oracle)."""
    if self.clock is None:
      return 1
    return 2 if self.clock.overlap else 0

  @property
  def transfers_in_flight(self) -> Tuple[int, ...]:
    """Rids whose fetch transfer has started but not been finalized."""
    return tuple(self._transfer_ready)

  def step(self) -> List[RequestHandle]:
    """Admit queued requests into free slots, run one batched decode step,
    and return the requests that finished this step.

    Spans (`repro.common.timing`): `engine.step` around the call, holding
    `engine.admit` (with an `engine.prefill` per admission),
    `engine.blocks`, `engine.decode` (`decode.launch`: stage the inputs
    and enqueue the program; `decode.wait`: the host blocked on the
    step's tokens) and `engine.commit`."""
    with timing.span("engine.step", step=self._step_no):
      self.stats.queue_depth_samples.append(len(self._queue))
      self._shard_fault_gate()
      finished = self._enforce_slo() if self.slo_enforce else []
      with timing.span("engine.admit"):
        finished.extend(self._admit())
      if self.active_count == 0:
        self._step_no += 1
        self.stats.steps += 1
        self._sync_clock_stats()
        return finished

      # every active row grows by one token this step; secure its block
      # first (may preempt-and-requeue under the paged scheduler, or shed
      # expired lowest-priority work under SLO enforcement)
      with timing.span("engine.blocks"):
        self._ensure_blocks(finished)
      if self.active_count == 0:          # everything preempted back to queue
        self._step_no += 1
        self.stats.steps += 1
        self._sync_clock_stats()
        return finished

      self._decode_fault_gate()
      with timing.span("engine.decode") as dec:
        with timing.span("decode.launch"):
          logits = self.layout.decode(self.params, self._cur, self._lengths)
        # np.asarray blocks on the device result: the span runs launch->sync
        with timing.span("decode.wait"):
          next_tok = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
      self.stats.decode_step_s.append(dec.seconds)
      with timing.span("engine.commit"):
        self._commit(next_tok, finished)
      return finished

  def _commit(self, next_tok: np.ndarray,
              finished: List[RequestHandle]) -> None:
    """Fold one decode step's tokens into the requests: append, finish,
    reclaim ring-retired blocks, refresh mirrors, start fetch-ahead."""
    if self.clock is not None:
      self.clock.advance(self.clock.decode_step_s)
    self.stats.decode_steps += 1
    self.stats.busy_slot_steps += self.active_count
    self.stats.wasted_slot_steps += self.max_batch - self.active_count

    for slot, req in enumerate(self._slots):
      if req is None:
        continue
      # the token we just fed (cur) is now cached at position lengths[slot]
      self._lengths[slot] += 1
      tok = int(next_tok[slot])
      req.tokens.append(tok)
      self._cur[slot] = tok
      if (len(req.tokens) >= req.max_new_tokens
          or int(self._lengths[slot]) + 1 >= self.context_len):
        finished.append(self._finish(slot, req))
      else:
        # ring-reuse: hand back blocks the policy's own masking retired
        self.stats.blocks_reclaimed += self.layout.reclaim(
            slot, int(self._lengths[slot]))
    self._mirror_sync()
    self._fetch_ahead()
    self._step_no += 1
    self.stats.steps += 1
    self._sync_clock_stats()

  def run_to_completion(self, max_steps: int = 10_000) -> List[RequestHandle]:
    """Drive `step()` until queue and slots drain; returns finish order."""
    done: List[RequestHandle] = []
    steps = 0
    while self.has_work:
      done.extend(self.step())
      steps += 1
      if steps > max_steps:
        raise RuntimeError(f"engine did not drain within {max_steps} steps")
    return done

  # -------------------------------------------------------------------------
  # internals
  # -------------------------------------------------------------------------

  def _admit(self) -> List[RequestHandle]:
    """Prefill (fresh) or fetch (spilled) scheduler-picked requests into
    free slots.  If the engine is idle yet nothing is admissible, the only
    thing holding the pool is the prefix cache itself — evict its coldest
    entries until admission unblocks (liveness over cache retention)."""
    finished = self._admit_pass()
    if (self.prefix_cache and not finished and self.active_count == 0
        and self._queue):
      evicted = False
      while True:
        # fifo/sjf pick without gating on admissibility, so check the
        # picked request itself — pick() is None is not the only stall
        idx = self.scheduler.pick(self._queue, self)
        if idx is not None and self.admissible(self._queue[idx]):
          break
        if not self.layout.prefix_evict_one():
          break
        evicted = True
      if evicted:
        finished.extend(self._admit_pass())
    # overlap-mode liveness: if nothing is running and the only admissible
    # work is behind an in-flight transfer, time must jump to the earliest
    # completion — otherwise an idle engine would spin at a frozen clock
    if (self.clock is not None and not self.active_count and self._queue
        and self._transfer_ready):
      self.clock.stall_until(min(self._transfer_ready.values()))
      finished.extend(self._admit_pass())
    return finished

  def _admit_pass(self) -> List[RequestHandle]:
    finished = []
    free_slots = [s for s, r in enumerate(self._slots) if r is None]
    while free_slots and self._queue:
      idx = self.scheduler.pick(self._queue, self)
      if idx is None:
        break
      req = self._queue[idx]
      if req.spilled:
        # swap-in: the request's KV survived on the host tier; restore it
        # and resume decoding exactly where the swap-out left off
        if not self.layout.can_fetch(req.rid,
                                     req.prompt_len + req.max_new_tokens):
          break                     # wait for running requests to free blocks
        if not self._transfer_ready_ok(req.rid):
          break                     # transfer still draining; decode goes on
        fate = self._fetch_fault(req)
        if fate is not None:
          # the transfer "failed": roll any IN_FLIGHT blocks back to the
          # host tier and either retry from the queue tail (transient) or
          # drop the request after bounded retries — never crash the step
          del self._queue[idx]
          self.layout.abort_prefetch(req.rid)
          self._transfer_ready.pop(req.rid, None)
          self.stats.fetch_failures += 1
          if fate == "drop":
            self.layout.drop_spilled(req.rid)
            req.failed = True
            req.done = True
            req.finished_step = self._step_no
            if self.clock is not None:
              req.finish_s = self.clock.now
            self.stats.failed_requests += 1
            finished.append(req)
          else:
            self._queue.append(req)
          self._sync_transfer_stats()
          continue
        del self._queue[idx]
        slot = free_slots.pop(0)
        ready = self._transfer_ready.pop(req.rid, None)
        ledger = getattr(self.layout, "ledger", None)
        before = ledger.total_bytes if ledger is not None else 0
        try:
          self.layout.fetch(req.rid, slot)
        except tiersmod.SpillPageCorruption:
          # the host copy is damaged: drop it and requeue for a recompute
          # prefill — greedy decoding regenerates identical tokens
          self._recover_corrupt(req)
          self._queue.append(req)
          free_slots.insert(0, slot)
          continue
        if self.clock is not None:
          if ready is not None:
            self.clock.stall_until(ready)   # no-op: readiness gated above
          elif ledger is not None:
            # no fetch-ahead happened: the transfer serializes right here
            moved = ledger.total_bytes - before
            self.clock.stall_until(
                self.clock.start_transfer(ledger.transfer_s(moved)))
        req.spilled = False
        req.slot = slot
        req.admitted_step = self._step_no
        self._slots[slot] = req
        self._lengths[slot] = req.resume_len
        self._cur[slot] = req.resume_cur
        self.stats.admits += 1
        self.stats.fetches += 1
        self._sync_transfer_stats()
        continue
      total = req.prompt_len + req.max_new_tokens
      plan = None
      if self.prefix_cache:
        # touch=True: this is the real admission — refresh matched entries'
        # LRU recency (scheduler probes are read-only)
        plan = self.layout.prefix_plan(req.prompt, total, touch=True)
        if plan["need"] > self.layout.free_blocks:
          break                     # wait for running requests to free blocks
      elif not self.layout.can_admit(req.prompt_len, total):
        break                       # wait for running requests to free blocks
      del self._queue[idx]
      slot = free_slots.pop(0)
      if req.submitted_step is not None:
        self.stats.queue_wait_steps.append(
            self._step_no - req.submitted_step)
      if self.clock is not None and req.admit_s is None:
        req.admit_s = self.clock.now   # queueing delay ends; prefill starts
      with timing.span("engine.prefill", rid=req.rid):
        first = self._prefill_into(slot, req, plan)
      req.slot = slot
      req.admitted_step = self._step_no
      req.tokens.append(first)
      self._slots[slot] = req
      self._lengths[slot] = req.prompt_len
      self._cur[slot] = first
      self.stats.admits += 1
      self._sync_prefix_stats()
      if len(req.tokens) >= req.max_new_tokens:
        finished.append(self._finish(slot, req))
        free_slots.insert(0, slot)
    return finished

  def _prefill_into(self, slot: int, req: RequestHandle,
                    plan: Optional[dict]) -> int:
    """Build the slot's KV for this prompt along the cheapest correct path:
    a whole-prompt snapshot (zero prefill), a shared chain + suffix-only
    chunked prefill, or the ordinary full prefill.  Returns the first
    greedy token; bit-identical across all three paths by construction."""
    p_len = req.prompt_len
    if plan is not None and plan["kind"] == "full":
      entry = plan["entry"]
      self.layout.admit_from_full(slot, entry)
      self.stats.prefix_hits += 1
      self.stats.prefix_full_hits += 1
      self.stats.prefix_hit_tokens += p_len
      self.layout.prefix_index.record_hit(p_len, full=True)
      self._charge_prefill(req, 0)      # snapshot hit: zero compute
      return int(entry.first_token)
    if plan is not None and plan["kind"] == "chain":
      matched = plan["matched_tokens"]
      self.layout.admit_shared(slot, plan["match"], p_len)
      first = self._prefill_suffix(slot, req, matched)
      self.stats.prefix_hits += 1
      self.stats.prefix_hit_tokens += matched
      self.stats.prefill_tokens += p_len - matched
      self.layout.prefix_index.record_hit(matched)
      self.layout.prefix_publish(slot, req.prompt, first)
      self._charge_prefill(req, p_len - matched)
      return first
    padded = np.zeros((1, self.prompt_capacity), np.int32)
    padded[0, :p_len] = req.prompt
    logits, slot_cache = self._prefill(
        self.params, jnp.asarray(padded), jnp.asarray([p_len], jnp.int32))
    self.layout.admit(slot, slot_cache, p_len)
    first = int(np.asarray(jnp.argmax(logits[0], axis=-1)))
    self.stats.prefill_tokens += p_len
    if self.prefix_cache:
      self.layout.prefix_publish(slot, req.prompt, first)
    self._charge_prefill(req, p_len)
    return first

  def _charge_prefill(self, req: RequestHandle, computed_tokens: int) -> None:
    """Spend virtual time on the tokens this admission actually computed
    (zero for a snapshot hit, the suffix for a chain hit) and stamp the
    request's first-token time — TTFT ends here."""
    if self.clock is None:
      return
    self.clock.advance(computed_tokens * self.clock.prefill_token_s)
    if req.first_token_s is None:
      req.first_token_s = self.clock.now

  def _prefill_suffix(self, slot: int, req: RequestHandle, start: int) -> int:
    """Suffix-only prefill: run the uncached prompt tail [start, prompt_len)
    through fixed-shape chunks against the slot's resident prefix KV.  One
    compile total (chunk shape is constant), any suffix length."""
    chunk = self._prefix_chunk
    p_len = req.prompt_len
    last_logits, last_start = None, start
    pos = start
    while pos < p_len:
      toks = np.zeros((1, chunk), np.int32)
      avail = req.prompt[pos:min(pos + chunk, p_len)]
      toks[0, :len(avail)] = avail
      last_logits = self.layout.prefill_chunk(self.params, slot, toks, pos)
      last_start = pos
      pos += chunk
    row = p_len - 1 - last_start
    return int(np.asarray(jnp.argmax(last_logits[0, row], axis=-1)))

  def _sync_prefix_stats(self) -> None:
    if not self.prefix_cache:
      return
    self.stats.forked_blocks = self.layout.forked_blocks
    by = self.layout.bytes(active_slots=self.active_count)
    self.stats.dedup_bytes = max(self.stats.dedup_bytes, by["dedup_bytes"])

  def clear_prefix_cache(self) -> int:
    """Drop every published prefix (frees the index's block holds)."""
    return self.layout.prefix_clear() if self.prefix_cache else 0

  def _ensure_blocks(self, finished: Optional[List[RequestHandle]] = None
                     ) -> None:
    """Grow every active slot's block table to hold this step's token,
    preempting (scheduler permitting) when the pool runs dry.  An injected
    allocator-exhaustion spike transiently reserves blocks, forcing the
    same spill/preempt/shed machinery a genuinely tight pool exercises —
    the reserve is never actually allocated, so it can never leak."""
    reserve = 0
    inj = self.fault_injector
    if inj is not None and hasattr(inj, "alloc_spike"):
      reserve = inj.alloc_spike(self._step_no)
      if reserve:
        self.stats.alloc_spikes += 1
    while True:
      growers = [(slot, self.layout.need_blocks(slot, int(ln) + 1))
                 for slot, ln in enumerate(self._lengths)
                 if self._slots[slot] is not None]
      total_need = sum(n for _, n in growers)
      if total_need <= max(self.layout.free_blocks - reserve, 0):
        for slot, need in growers:
          if need and not self.layout.ensure(
              slot, int(self._lengths[slot]) + 1):
            raise AssertionError("pool accounting drifted during growth")
        return
      if self.prefix_cache and self.layout.prefix_evict_one():
        continue      # prefer dropping cold cached prefixes over victims
      if self.slo_enforce and finished is not None:
        # shed the lowest-priority deadline-missed active request before
        # stalling or preempting everyone: its tokens can no longer count
        # toward goodput, so its blocks are the cheapest relief available
        shed = self._shed_expired_active(finished)
        if shed:
          continue
      victim = self.scheduler.on_exhausted(self)
      if victim is None:
        raise RuntimeError(
            f"KV block pool exhausted (need {total_need}, free "
            f"{self.layout.free_blocks}) and scheduler "
            f"{self.scheduler.name!r} cannot preempt; use --scheduler "
            f"paged/tiered or a larger --num-blocks")
      if self.scheduler.spills and self.layout.can_spill(victim):
        self._swap_out(victim)
      else:
        # host tier full (or single-tier layout): recompute preemption
        self._preempt(victim)

  def _swap_out(self, slot: int) -> None:
    """Swap preemption: the victim's KV moves to the host tier through the
    policy's spill codecs; its generated tokens are kept and decoding
    resumes from the same position after a later fetch."""
    req = self._slots[slot]
    assert req is not None, f"swapping out empty slot {slot}"
    req.resume_len = int(self._lengths[slot])
    req.resume_cur = int(self._cur[slot])
    ledger = getattr(self.layout, "ledger", None)
    before = ledger.total_bytes if ledger is not None else 0
    self.layout.spill(slot, req.rid, req.resume_len)
    inj = self.fault_injector
    if (inj is not None and hasattr(inj, "should_corrupt_spill")
        and inj.should_corrupt_spill(req.rid, req.spill_count)):
      # damage the page now; detection happens at fetch via the frame
      # checksum, recovery via recompute-prefill (_recover_corrupt)
      self.layout.corrupt_spilled(req.rid)
    if self.clock is not None and ledger is not None:
      # the spill occupies the link (overlapped with decode, or a stall in
      # serialized mode); the device blocks are free either way — nothing
      # waits on a spill's completion
      self.clock.start_transfer(
          ledger.transfer_s(ledger.total_bytes - before))
    req.spilled = True
    req.slot = None
    req.spill_count += 1
    self._slots[slot] = None
    self._lengths[slot] = 0
    self._cur[slot] = 0
    self._queue.appendleft(req)
    self.stats.spills += 1
    self._sync_transfer_stats()

  def _fetch_ahead(self) -> None:
    """Start materializing upcoming spilled requests' blocks (IN_FLIGHT) so
    their admits only finalize.  Wall-clock engines keep the PR 3 one-step
    hint; under an overlapping virtual clock this is a double-buffered
    async stage — up to `fetch_depth` transfers drain on the modeled PCIe
    link while decode proceeds on resident requests, each completing at a
    deadline drawn from `TransferLedger.transfer_s`."""
    if self.clock is None:
      rid = self.scheduler.fetch_ahead(self)
      if rid is not None and self._prefetch_checked(rid):
        self.stats.prefetches += 1
        self._sync_transfer_stats()
      return
    depth = self.fetch_depth
    if depth == 0:
      return                        # serialized fallback: no async stage
    ledger = getattr(self.layout, "ledger", None)
    if ledger is None:
      return                        # single-tier layout: nothing to fetch
    for rid in self.scheduler.fetch_ahead_many(self, depth):
      if len(self._transfer_ready) >= depth:
        break
      if rid in self._transfer_ready:
        continue
      before = ledger.total_bytes
      if self._prefetch_checked(rid):
        self._transfer_ready[rid] = self.clock.start_transfer(
            ledger.transfer_s(ledger.total_bytes - before))
        self.stats.prefetches += 1
    self._sync_transfer_stats()

  def _prefetch_checked(self, rid: int) -> bool:
    """`layout.prefetch` with corrupted-page recovery: on a checksum
    mismatch the host copy is dropped and the (still queued) request is
    reset for a recompute prefill.  Returns False — no transfer started."""
    try:
      return self.layout.prefetch(rid)
    except tiersmod.SpillPageCorruption:
      req = next((r for r in self._queue if r.rid == rid), None)
      if req is not None:
        self._recover_corrupt(req)
      return False

  def _transfer_ready_ok(self, rid: int) -> bool:
    """May this spilled request finalize its fetch now?  True unless an
    overlapped transfer for it is still draining on the link."""
    if self.clock is None or not self.clock.overlap:
      return True
    ready = self._transfer_ready.get(rid)
    return ready is None or ready <= self.clock.now + 1e-12

  def _fetch_fault(self, req: RequestHandle) -> Optional[str]:
    """Consult the fault injector about this fetch attempt: None (proceed),
    'retry' (transient fault, requeue), or 'drop' (retries exhausted)."""
    if self.fault_injector is None:
      return None
    try:
      self.fault_injector.check_fetch(req.rid, req.fetch_failures)
    except fault_tolerance.SimulatedFailure:
      req.fetch_failures += 1
      if req.fetch_failures > self.max_fetch_retries:
        return "drop"
      return "retry"
    return None

  def _recover_corrupt(self, req: RequestHandle) -> None:
    """Recover a request whose spilled page failed its checksum: the host
    copy is unrecoverable, so drop it (freeing both tiers) and reset the
    handle for a recompute prefill from the prompt — under greedy decoding
    the regenerated tokens are bit-identical to the lost ones."""
    self.layout.abort_prefetch(req.rid)       # no-op unless IN_FLIGHT
    self._transfer_ready.pop(req.rid, None)
    self.layout.drop_spilled(req.rid)
    req.spilled = False
    req.tokens = []
    req.resume_len = 0
    req.resume_cur = 0
    req.admit_s = None
    req.first_token_s = None
    req.preempt_count += 1
    self.stats.corrupt_pages += 1
    self._sync_transfer_stats()

  def _decode_fault_gate(self) -> None:
    """Transient decode-step fault injection with bounded retry/backoff:
    each failed attempt burns one decode step of virtual time (the retry's
    cost) and re-draws; past `max_decode_retries` the fault is treated as
    persistent and surfaces."""
    inj = self.fault_injector
    if inj is None or not hasattr(inj, "check_decode"):
      return
    attempt = 0
    while inj.check_decode(self._step_no, attempt):
      attempt += 1
      self.stats.decode_faults += 1
      if self.clock is not None:
        self.clock.advance(self.clock.decode_step_s)   # retry backoff
      if attempt > self.max_decode_retries:
        raise fault_tolerance.SimulatedFailure(
            f"decode step {self._step_no} failed "
            f"{attempt} consecutive attempts")

  # -- shard fault tolerance (PR 10) -----------------------------------------

  def _shard_fault_gate(self) -> None:
    """One watchdog heartbeat round per engine step.

    The injector's shard surfaces fire first (a stalled shard misses this
    round and costs the synchronous mesh one step of virtual time; a lost
    shard stops beating permanently), then `ShardHealth.record` confirms
    deaths after `confirm_after` consecutive misses and the engine runs
    the recovery path for each.
    """
    inj = self.fault_injector
    health = self.shard_health
    if inj is not None:
      if hasattr(inj, "shard_stall"):
        s = inj.shard_stall(self._step_no, health.shards)
        if s is not None:
          health.mark_stalled(s)
          self.stats.shard_stalls += 1
          if self.clock is not None:
            # a synchronous mesh decodes at the pace of its slowest shard:
            # one straggler charges everyone one extra step
            self.clock.advance(self.clock.decode_step_s)
      if hasattr(inj, "shard_loss"):
        s = inj.shard_loss(self._step_no, health.shards)
        if s is not None:
          health.mark_lost(s)
    dead = health.record()
    self.stats.shard_heartbeats = list(health.beats)
    if dead:
      self._recover_shard_loss(dead)

  def _recover_shard_loss(self, dead: List[int]) -> None:
    """Confirmed shard death: drain, damage, replan, recover.

    1. Drain — every overlapped fetch rolls back to SPILLED (its transfer
       may have involved the dead shard).
    2. Damage model — heads mode shards a kv-head slice of *every* pool
       block, so a dead shard voids all resident data (the storage is
       scrubbed to make recovery falsifiable); seq and none replicate
       storage, so survivors keep full copies and only the plan changes.
    3. Replan — `ShardPlan.replan(survivors)` re-partitions over the
       surviving subset and the layout re-places storage + re-binds its
       decode programs; params re-commit to the survivor submesh.
    4. Recover — with data lost, each active slot restores from its host
       mirror (checksum-verified) or resets for a recompute prefill;
       spilled requests whose pinned shared blocks were damaged recompute
       too.  Requests are recovered, never aborted.
    """
    plan = self.shard_plan
    self.stats.shard_losses += len(dead)
    self.stats.dead_shards.extend(int(s) for s in dead)
    for rid in list(self._transfer_ready):
      self.layout.abort_prefetch(rid)
    self._transfer_ready.clear()
    self._sync_transfer_stats()
    lost_data = plan is None or not plan.active or plan.mode == "heads"
    if lost_data and hasattr(self.layout, "damage_storage"):
      self.layout.damage_storage()
    n_after = 1
    if plan is not None and plan.active:
      survivors = [i for i in range(plan.size) if i not in set(dead)]
      new_plan = plan.replan(survivors)
      self.layout.replan(new_plan)
      self.shard_plan = new_plan
      # the replicated network must re-commit to the survivor submesh —
      # GSPMD would otherwise see params placed on a dead device, and an
      # inactive fallback plan still re-places storage, so prefill outputs
      # must land on the same submesh
      self.params = ssh.replicate(self.params, new_plan)
      self.stats.mesh_shards = new_plan.size
      self.stats.mesh_mode = new_plan.mode
      self.stats.shard_replans += 1
      n_after = new_plan.size if new_plan.active else 1
    # the watchdog re-bases on the new plan's shard indices (a replanned
    # mesh numbers its shards from zero; stale lost marks must not
    # re-confirm against the survivors)
    self.shard_health = ssh.ShardHealth(
        n_after, confirm_after=self.shard_health.confirm_after)
    if lost_data:
      self._recover_lost_data()

  def _recover_lost_data(self) -> None:
    """Rebuild every resident request after whole-pool data damage."""
    if self.prefix_cache:
      # index-held blocks have no owning request to recompute them; the
      # cache rebuilds warm as recovered requests re-publish
      self.layout.prefix_clear()
    restored_blocks: set = set()
    recompute: List[RequestHandle] = []
    mirrored = getattr(self.layout, "mirror", None) is not None
    ledger = getattr(self.layout, "ledger", None)
    for slot, req in self.active_requests:
      rec = None
      if mirrored:
        try:
          rec = self.layout.mirror_restore(slot)
        except tiersmod.SpillPageCorruption:
          rec = None                  # damaged mirror page: fall back
      self.stats.shard_recovered_requests += 1
      if rec is not None:
        restored_blocks.update(rec.device_block_ids)
        self.stats.shard_mirror_restores += 1
        if self.clock is not None and ledger is not None:
          # the restore transfer blocks the slot's next decode step
          self.clock.stall_until(
              self.clock.start_transfer(ledger.transfer_s(rec.nbytes)))
        continue
      # recompute path: release the slot and reset the handle — greedy
      # decoding regenerates the identical tokens on re-admission
      req.tokens = []
      req.slot = None
      req.admitted_step = None
      req.admit_s = None
      req.first_token_s = None
      req.preempt_count += 1
      self.layout.release(slot)
      self._slots[slot] = None
      self._lengths[slot] = 0
      self._cur[slot] = 0
      self.stats.preempts += 1
      recompute.append(req)
    # spilled requests: their payloads live on the host tier (safe), but
    # pinned shared-prefix blocks sit in the damaged device pool — resume
    # only when every pin was mirror-restored, else recompute
    if hasattr(self.layout, "spill_pins"):
      for req in self._queue:
        if not req.spilled:
          continue
        pins = set(self.layout.spill_pins(req.rid))
        if pins and not pins <= restored_blocks:
          self.layout.abort_prefetch(req.rid)
          self.layout.drop_spilled(req.rid)
          req.spilled = False
          req.tokens = []
          req.resume_len = 0
          req.resume_cur = 0
          req.admit_s = None
          req.first_token_s = None
          req.preempt_count += 1
          self.stats.shard_recovered_requests += 1
    if recompute:
      ordered = list(self.scheduler.shard_recovery_requeue(self, recompute))
      for req in reversed(ordered):
        self._queue.appendleft(req)
    self._sync_transfer_stats()

  def _mirror_sync(self) -> None:
    """Write-through refresh of every active slot's host mirror.  Mirror
    writes ride a dedicated host path overlapped with the next decode step,
    so no virtual time is charged; restores are what stall (and are charged
    at `_recover_lost_data`)."""
    if getattr(self.layout, "mirror", None) is None:
      return
    for slot, req in self.active_requests:
      self.layout.mirror_sync(slot, req.rid, int(self._lengths[slot]))

  def shard_health_info(self) -> dict:
    """Stats-json `shard_health` section: watchdog state, recovery
    counters, and the host mirror's footprint."""
    info = self.shard_health.as_dict()
    info.update(
        redundancy=getattr(self.layout, "shard_redundancy", "none"),
        losses=self.stats.shard_losses,
        stalls=self.stats.shard_stalls,
        replans=self.stats.shard_replans,
        mirror_restores=self.stats.shard_mirror_restores,
        recovered_requests=self.stats.shard_recovered_requests,
        dead_shards=list(self.stats.dead_shards),
        mesh_shards=self.stats.mesh_shards,
        mesh_mode=self.stats.mesh_mode)
    mirror = getattr(self.layout, "mirror", None)
    if mirror is not None:
      info["mirror"] = mirror.as_dict()
    return info

  # -- SLO enforcement + graceful degradation --------------------------------

  def _enforce_slo(self) -> List[RequestHandle]:
    """Deadline admission control, run once per step before admits: update
    the degradation state machine, shed queued requests that already missed
    their deadline (their tokens can never count toward goodput), and under
    SHEDDING also those that provably cannot make it even at full speed."""
    finished: List[RequestHandle] = []
    if self.clock is None:
      return finished
    total = max(self.layout.num_blocks, 1) if hasattr(
        self.layout, "num_blocks") else 1
    free_frac = self.layout.free_blocks / total if hasattr(
        self.layout, "free_blocks") else 1.0
    trans = self._degradation.observe(free_frac, len(self._queue))
    if trans is not None:
      self.stats.degradation_state = trans[1]
      if len(self.stats.degradation_transitions) < 256:
        self.stats.degradation_transitions.append(dict(
            step=self._step_no, virtual_s=round(self.clock.now, 6),
            old=trans[0], new=trans[1],
            free_frac=round(free_frac, 4), queue_depth=len(self._queue)))
    state = self._degradation.state
    if state == "PRESSURED" and self.prefix_cache:
      # progressive degradation: give back one cold cached prefix per
      # pressured step instead of waiting for hard exhaustion
      self.layout.prefix_evict_one()
    now = self.clock.now
    for req in [r for r in self._queue if r.deadline_s is not None]:
      doomed = now >= req.deadline_s
      if not doomed and state == "SHEDDING":
        # lower bound: every remaining token costs at least one decode
        # step — if even that misses the deadline, the request is doomed
        doomed = (now + req.max_new_tokens * self.clock.decode_step_s
                  > req.deadline_s)
      if doomed:
        self._queue.remove(req)
        finished.append(self._cancel_queued(req))
    return finished

  def _cancel_queued(self, req: RequestHandle) -> RequestHandle:
    """Cleanly cancel a queued request: reclaim any in-flight transfer,
    host-tier pages, and shared-prefix pins it holds, then mark it shed."""
    if req.spilled:
      self.layout.abort_prefetch(req.rid)
      self._transfer_ready.pop(req.rid, None)
      self.layout.drop_spilled(req.rid)
      req.spilled = False
      self._sync_transfer_stats()
    req.shed = True
    req.done = True
    req.finished_step = self._step_no
    if self.clock is not None:
      req.finish_s = self.clock.now
    self.stats.shed_requests += 1
    return req

  def _shed_expired_active(self, finished: List[RequestHandle]) -> bool:
    """Under pool pressure, cancel the lowest-priority *active* request
    whose deadline already passed (its remaining tokens are worthless);
    frees its blocks instead of spilling/preempting still-viable work."""
    if self.clock is None:
      return False
    now = self.clock.now
    expired = [(r.priority, -(r.admitted_step or 0), s, r)
               for s, r in self.active_requests
               if r.deadline_s is not None and now >= r.deadline_s]
    if not expired:
      return False
    expired.sort(key=lambda t: (t[0], t[1], t[2]))
    _, _, slot, req = expired[0]
    self.layout.release(slot)
    self._slots[slot] = None
    self._lengths[slot] = 0
    self._cur[slot] = 0
    req.slot = None
    req.shed = True
    req.done = True
    req.finished_step = self._step_no
    req.finish_s = now
    self.stats.shed_requests += 1
    self.stats.pressure_sheds += 1
    finished.append(req)
    return True

  # -- crash-safe snapshot/restore -------------------------------------------

  def save_snapshot(self, step: int = 0) -> Optional[str]:
    """Persist the prefix cache (trie + pinned block contents) through
    `checkpoint/ckpt.py` so a restarted engine serves warm prefix hits.
    Returns the checkpoint directory, or None when there is nothing to
    snapshot (no snapshot_dir or prefix cache disabled)."""
    if not (self.snapshot_dir and self.prefix_cache):
      return None
    tree, extra = self.layout.prefix_snapshot()
    return ckpt_lib.save(self.snapshot_dir, step, tree, extra=extra)

  def _sync_transfer_stats(self) -> None:
    ledger = getattr(self.layout, "ledger", None)
    if ledger is not None:
      self.stats.spill_bytes = ledger.spill_bytes
      self.stats.fetch_bytes = ledger.fetch_bytes
      self.stats.modeled_pcie_s = ledger.modeled_pcie_s
      self.stats.fetch_aborts = ledger.fetch_aborts

  def _sync_clock_stats(self) -> None:
    c = self.clock
    if c is None:
      return
    self.stats.virtual_s = c.now
    self.stats.compute_s = c.compute_s
    self.stats.transfer_stall_s = c.transfer_stall_s
    self.stats.idle_s = c.idle_s
    self.stats.link_busy_s = c.link_busy_s

  def _preempt(self, slot: int) -> None:
    """Recompute preemption: release the slot, requeue the request; greedy
    decoding regenerates its tokens identically on re-admission."""
    req = self._slots[slot]
    assert req is not None, f"preempting empty slot {slot}"
    req.tokens = []
    req.slot = None
    req.admitted_step = None
    req.admit_s = None               # re-admission re-measures queueing
    req.first_token_s = None         # regenerated tokens re-stamp TTFT
    req.preempt_count += 1
    self.layout.release(slot)
    self._slots[slot] = None
    self._lengths[slot] = 0
    self._cur[slot] = 0
    self._queue.appendleft(req)
    self.stats.preempts += 1

  def _finish(self, slot: int, req: RequestHandle) -> RequestHandle:
    req.done = True
    req.finished_step = self._step_no
    if self.clock is not None:
      req.finish_s = self.clock.now
    self.layout.release(slot)
    self._slots[slot] = None
    self._lengths[slot] = 0
    self._cur[slot] = 0
    self.stats.finished += 1
    return req
