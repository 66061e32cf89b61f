"""Sharded multi-device serving: mesh-partitioned block pools + cross-shard decode.

AQPIM's serve path (PR 1-6) is single-device; the PIM systems it competes
with (PIMphony / LoL-PIM) get their headline numbers by spreading attention
across channels/ranks.  The software analogue here partitions the paged KV
block pool over a named JAX mesh and runs the decode step under `shard_map`,
with host-side orchestration (block tables, admission, spill/fetch, the
prefix index) untouched — one global `BlockTableManager` keeps issuing the
same tables; only where the pool *bytes* live and who computes which heads
changes.

Two partition modes, picked by the same fallback-chain doctrine as
`parallel.sharding._choose` (first candidate whose dims divide wins):

``heads``   kv heads over the `model` axis when `n_kv_heads % size == 0`.
            Every pool leaf `(P+1, L, H, block, ...)` and resident leaf
            `(L, B, H, ...)` carries kv heads at axis 2, so one rule shards
            the whole policy-state tree.  Inside the decode step each shard
            computes q/k/v from replicated activations, slices its own
            kv-head range (GQA query groups follow their kv head), runs the
            policy's unmodified attend on its local heads, and an ordered
            `all_gather(..., tiled=True)` reassembles the per-head attention
            context before the replicated `wo` projection.  Per-kv-head
            attention is fully independent in every policy, concatenation is
            exact, and the post-attention network is replicated — greedy
            tokens are **bit-identical** to single-device, for every cache
            policy.

``seq``     flash-decoding split-K over the sequence axis when heads don't
            divide.  Each shard owns a contiguous chunk of token positions,
            computes partial-softmax `(out, max, denom)` stats over (owned
            positions) ∩ (valid positions), and the stats are all-gathered
            and merged in fixed shard order through the same exact
            `kernels.ops.combine_attention_segments` PR 5 uses for the PQ
            sink/recent segments.  Storage stays replicated (the terminal
            fallback of the `_choose` chain); the combine is mathematically
            exact but reassociates floating point, so this mode carries the
            same empirical token-identity bar PR 5 applied across kernels
            rather than a bit-identity guarantee.  Exact policy only —
            compressed policies couple eviction to position and need the
            heads mode (plan_for raises with the fallback chain named).

Mode ``none`` (mesh model axis of 1) is the plain unsharded path: no
shard_map, no collectives, byte-for-byte the PR 6 programs.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel.sharding import MODEL_AXIS


@dataclasses.dataclass(frozen=True)
class ShardPlan:
  """Resolved decode-sharding decision for one serve run.

  Frozen at engine/layout construction (like `DecodeDispatch`): the serve
  loop compiles exactly one decode program per run, with no per-step
  branching on mesh state.
  """
  mesh: Mesh
  axis: str = MODEL_AXIS
  mode: str = "none"            # "none" | "heads" | "seq"
  size: int = 1                 # shards along `axis`
  n_kv_heads: int = 0
  n_heads: int = 0
  policy: str = "exact"         # resolved cache policy; gates the seq link

  @property
  def active(self) -> bool:
    return self.mode != "none" and self.size > 1

  @property
  def bit_identical(self) -> bool:
    """Does this plan guarantee bit-identical greedy tokens vs mesh=1?"""
    return self.mode in ("none", "heads")

  def describe(self) -> dict:
    return dict(axis=self.axis, mode=self.mode, shards=self.size,
                devices=[str(d) for d in self.mesh.devices.reshape(-1)],
                bit_identical=self.bit_identical)

  def replan(self, survivors) -> "ShardPlan":
    """Degraded-mesh plan over the surviving shards after confirmed deaths.

    Same fallback-chain doctrine as `plan_for`, re-run against what is
    left: **heads** over the largest divisor-compatible survivor subset
    (kv heads re-partition; extra survivors idle rather than breaking
    divisibility), else **seq** split-K over every survivor when the
    policy supports it, else **single-device** on the first survivor.
    Survivor indices are shard positions along `self.axis` in the current
    plan; the returned plan's mesh is a submesh of the current one
    (`parallel.sharding.survivor_submesh`), so re-placing storage with
    `place_storage` moves the pool onto the survivors.
    """
    from repro.parallel.sharding import survivor_submesh
    surv = sorted(set(int(s) for s in survivors))
    if not surv:
      raise ValueError("cannot replan with no surviving shards")
    if any(s < 0 or s >= max(self.size, 1) for s in surv):
      raise ValueError(f"survivors {surv} out of range for a "
                       f"{self.size}-shard plan")
    n = len(surv)
    k = max((d for d in range(2, n + 1)
             if self.n_kv_heads > 0 and self.n_kv_heads % d == 0),
            default=1)
    if k > 1:
      mesh = survivor_submesh(self.mesh, self.axis, surv[:k])
      return dataclasses.replace(self, mesh=mesh, mode="heads", size=k)
    if n > 1 and self.policy in _SEQ_CAPABLE_POLICIES:
      mesh = survivor_submesh(self.mesh, self.axis, surv)
      return dataclasses.replace(self, mesh=mesh, mode="seq", size=n)
    mesh = survivor_submesh(self.mesh, self.axis, surv[:1])
    return dataclasses.replace(self, mesh=mesh, mode="none", size=1)


# Policies whose decode attend the seq split-K path can drive: the split
# masks positions inside a plain exact-store softmax.  Compressed/windowed
# policies couple eviction and encoding to absolute position and are heads-
# mode only.
_SEQ_CAPABLE_POLICIES = ("exact",)


def plan_for(cfg, mesh: Mesh, *, axis: str = MODEL_AXIS) -> ShardPlan:
  """Pick the partition mode for this (config, mesh) — fallback-chain style.

  Mirrors `parallel.sharding._choose`: candidates in preference order, first
  one whose divisibility holds wins; an impossible chain raises with every
  link named instead of silently replicating a pool the caller asked to
  shard.
  """
  size = int(dict(mesh.shape).get(axis, 1))
  policy = cfg.resolved_cache_policy()
  if size <= 1:
    return ShardPlan(mesh=mesh, axis=axis, mode="none", size=1,
                     n_kv_heads=cfg.n_kv_heads, n_heads=cfg.n_heads,
                     policy=policy)
  if cfg.n_kv_heads % size == 0:
    mode = "heads"
  elif policy in _SEQ_CAPABLE_POLICIES:
    mode = "seq"
  else:
    raise ValueError(
        f"cannot shard decode for policy {policy!r} over {axis}={size}: "
        f"kv heads ({cfg.n_kv_heads}) are not divisible by the axis, and "
        f"the sequence split-K fallback supports only policies "
        f"{_SEQ_CAPABLE_POLICIES} (compressed policies couple eviction to "
        f"position); pick a mesh model axis dividing {cfg.n_kv_heads}")
  return ShardPlan(mesh=mesh, axis=axis, mode=mode, size=size,
                   n_kv_heads=cfg.n_kv_heads, n_heads=cfg.n_heads,
                   policy=policy)


# ---------------------------------------------------------------------------
# Trace-time shard context
#
# The decode programs live behind `Model.decode_step` / `decode_step_paged`
# and a `jax.lax.scan` over layers; threading a plan argument through every
# signature would churn the whole model API for a serve-only concern.
# Instead the layout activates the plan around *tracing* its shard_map body,
# and the attention seam (`models.transformer._attn_step*`) consults it.
# Purely trace-time state: the compiled program bakes the decision in.
# ---------------------------------------------------------------------------

_ACTIVE: Optional[ShardPlan] = None


@contextlib.contextmanager
def activate(plan: Optional[ShardPlan]):
  global _ACTIVE
  prev = _ACTIVE
  _ACTIVE = plan if (plan is not None and plan.active) else None
  try:
    yield
  finally:
    _ACTIVE = prev


def active_plan() -> Optional[ShardPlan]:
  return _ACTIVE


# ---------------------------------------------------------------------------
# Storage placement
# ---------------------------------------------------------------------------

def storage_pspec(plan: ShardPlan, leaf) -> P:
  """Partition rule for one decode-storage leaf.

  Both storage families carry kv heads at axis 2 — pool leaves
  `(P+1, L, H, block, ...)` and resident leaves `(L, B, H, ...)` — so heads
  mode is one spec; seq mode replicates storage (the split is over compute).
  """
  nd = leaf.ndim
  if (not plan.active or plan.mode != "heads" or nd < 3
      or leaf.shape[2] != plan.n_kv_heads):
    return P(*([None] * nd))
  return P(None, None, plan.axis, *([None] * (nd - 3)))


def storage_pspecs(plan: ShardPlan, storage: Any) -> Any:
  return jax.tree_util.tree_map(lambda lf: storage_pspec(plan, lf), storage)


def place_storage(storage: Any, plan: ShardPlan) -> Any:
  """Commit a freshly built storage tree to its mesh placement."""
  return jax.tree_util.tree_map(
      lambda lf: jax.device_put(
          lf, NamedSharding(plan.mesh, storage_pspec(plan, lf))), storage)


def replicate(tree: Any, plan: ShardPlan) -> Any:
  """Commit a tree (params) replicated over every mesh device."""
  return jax.tree_util.tree_map(
      lambda lf: jax.device_put(
          lf, NamedSharding(plan.mesh, P(*([None] * jnp.ndim(lf))))), tree)


def wrap_decode(decode_fn, plan: ShardPlan, storage_example: Any):
  """shard_map a `(params, cur, storage, tables, lengths) -> (logits,
  storage)` decode program under the plan.

  Everything except storage is replicated in and out; storage follows
  `storage_pspec` (head-partitioned pools in heads mode, replicated in seq
  mode).  The body runs the *unmodified* program — the attention seam reads
  the activated plan and does the per-shard slice / ordered all_gather (or
  split-K stats merge), so logits leave the body replicated.  check_vma is
  off: the replication of post-all_gather values is by construction, not
  provable by the varying-axes checker.
  """
  st_specs = storage_pspecs(plan, storage_example)

  def body(params, cur, storage, tables, lengths):
    with activate(plan):
      return decode_fn(params, cur, storage, tables, lengths)

  return jax.shard_map(
      body, mesh=plan.mesh,
      in_specs=(P(), P(), st_specs, P(), P()),
      out_specs=(P(), st_specs),
      check_vma=False)


# ---------------------------------------------------------------------------
# Heads mode: per-shard head slice + ordered context gather
# ---------------------------------------------------------------------------

def shard_attn_inputs(q, k, v, plan: ShardPlan):
  """Slice replicated q/k/v `(B, H*, d)` to this shard's kv-head range.

  GQA query heads are laid out kv-head-major (`q.reshape(h, g, d)` in every
  policy), so the query slice for kv heads [i*h_loc, (i+1)*h_loc) is the
  contiguous [i*h_loc*g, (i+1)*h_loc*g).
  """
  idx = jax.lax.axis_index(plan.axis)
  h_loc = plan.n_kv_heads // plan.size
  g = plan.n_heads // plan.n_kv_heads
  q = jax.lax.dynamic_slice_in_dim(q, idx * h_loc * g, h_loc * g, axis=1)
  k = jax.lax.dynamic_slice_in_dim(k, idx * h_loc, h_loc, axis=1)
  v = jax.lax.dynamic_slice_in_dim(v, idx * h_loc, h_loc, axis=1)
  return q, k, v


def gather_attn_outputs(attn, plan: ShardPlan):
  """Reassemble the full per-head attention context in shard order.

  tiled=True concatenates along the head axis; shard i contributed heads
  [i*h_loc*g, (i+1)*h_loc*g), so the result is exactly the unsharded
  `(B, Hq, d)` context — bitwise, since each head's values were computed by
  exactly one shard with single-device math.
  """
  return jax.lax.all_gather(attn, plan.axis, axis=1, tiled=True)


# ---------------------------------------------------------------------------
# Seq mode: flash-decoding split-K over token positions
# ---------------------------------------------------------------------------

def seq_append_and_attend(cache, q, k_new, v_new, lengths, scale,
                          plan: ShardPlan):
  """Exact-policy decode step, split-K over the sequence across shards.

  Cache leaves arrive replicated `(B, H, N, D)`; every shard performs the
  identical token insert (so storage stays replicated), then computes
  partial-softmax stats over (its contiguous position chunk) ∩ (pos <
  length+1).  Ownership chunks tile [0, N), so the union covers each valid
  position exactly once; an all-masked shard contributes the neutral
  (0, NEG_INF, 0) stats `segment_attention_stats` defines.  Stats are
  all-gathered and merged in fixed shard order via the exact PR 5 combine.
  """
  from repro.core import kv_cache as kvc
  from repro.core import pq_attention
  from repro.kernels import ops as kops

  b, hq, d = q.shape
  h = cache.k.shape[1]
  g = hq // h
  lengths = kvc.as_lengths(lengths, b)
  k_c, v_c = jax.vmap(kvc.exact_insert_one)(cache.k, cache.v, k_new, v_new,
                                            lengths)
  n_max = k_c.shape[2]
  idx = jax.lax.axis_index(plan.axis)
  chunk = -(-n_max // plan.size)
  pos = jnp.arange(n_max)
  owned = (pos >= idx * chunk) & (pos < (idx + 1) * chunk)

  qg = q.reshape(b, h, g, d)

  def per_req(qq, kk, vv, ln):
    mask = owned & (pos < ln + 1)
    return jax.vmap(
        lambda qh, kh, vh: pq_attention.segment_attention_stats(
            qh, kh, vh, mask, scale))(qq, kk, vv)

  out, mx, dn = jax.vmap(per_req)(qg, k_c, v_c, lengths)
  outs = jax.lax.all_gather(out, plan.axis)       # (S, B, H, g, D)
  mxs = jax.lax.all_gather(mx, plan.axis)
  dns = jax.lax.all_gather(dn, plan.axis)
  combined = kops.combine_attention_segments(
      [outs[i] for i in range(plan.size)],
      [mxs[i] for i in range(plan.size)],
      [dns[i] for i in range(plan.size)])
  return combined.reshape(b, hq, d), cache._replace(k=k_c, v=v_c)


# ---------------------------------------------------------------------------
# Shard health watchdog
# ---------------------------------------------------------------------------


class ShardHealth:
  """Per-shard decode heartbeat watchdog.

  The engine records one heartbeat round per serve step: every shard beats
  unless the fault injector marked it lost (it stops beating permanently)
  or stalled (it skips this round).  A shard that misses `confirm_after`
  consecutive rounds is confirmed dead exactly once — `record()` returns
  the newly confirmed ids and the engine drains in-flight transfers,
  replans over the survivors, and recovers affected requests.  A sustained
  stall therefore escalates to a loss, the standard watchdog semantics; a
  transient straggle just costs the mesh one step of virtual time.
  """

  def __init__(self, shards: int = 1, confirm_after: int = 2):
    self.shards = max(int(shards), 1)
    self.confirm_after = max(int(confirm_after), 1)
    self.beats = [0] * self.shards
    self.missed = [0] * self.shards
    self.lost: set = set()
    self.confirmed: set = set()
    self._stalled: set = set()

  def mark_lost(self, shard: int) -> None:
    """Shard stops heartbeating permanently (shard-loss injection)."""
    self.lost.add(int(shard))

  def mark_stalled(self, shard: int) -> None:
    """Shard misses the next heartbeat round only (shard-stall)."""
    self._stalled.add(int(shard))

  def record(self) -> list:
    """One heartbeat round; returns shard ids newly confirmed dead."""
    fresh = []
    for s in range(self.shards):
      if s in self.lost or s in self._stalled:
        self.missed[s] += 1
        if self.missed[s] >= self.confirm_after and s not in self.confirmed:
          self.confirmed.add(s)
          fresh.append(s)
      else:
        self.beats[s] += 1
        self.missed[s] = 0
    self._stalled.clear()
    return fresh

  def alive(self) -> list:
    return [s for s in range(self.shards) if s not in self.confirmed]

  def as_dict(self) -> dict:
    return dict(shards=self.shards, confirm_after=self.confirm_after,
                beats=list(self.beats), missed=list(self.missed),
                lost=sorted(self.lost), confirmed=sorted(self.confirmed))


# ---------------------------------------------------------------------------
# Accounting
# ---------------------------------------------------------------------------

def per_shard_bytes(plan: ShardPlan, storage: Any) -> dict:
  """Pool/resident bytes each shard actually holds.

  Heads mode divides every H-sharded leaf by the shard count; seq (and
  none) replicate storage, so per-shard equals total.  Derived from the
  same leaves `PagedLayout.bytes()` walks, so the two sections agree.
  """
  sharded = 0
  replicated = 0
  for lf in jax.tree_util.tree_leaves(storage):
    spec = storage_pspec(plan, lf)
    if any(ax is not None for ax in spec):
      sharded += lf.nbytes
    else:
      replicated += lf.nbytes
  size = plan.size if plan.active else 1
  return dict(
      mode=plan.mode, shards=size,
      total_bytes=sharded + replicated,
      sharded_bytes=sharded, replicated_bytes=replicated,
      bytes_per_shard=sharded // size + replicated)
