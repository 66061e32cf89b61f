"""Roofline analysis (spec: ROOFLINE ANALYSIS) — per (arch x shape x mesh):

  compute term    = FLOPs / (chips * peak FLOP/s)
  memory term     = HBM_bytes / (chips * HBM bytes/s)
  collective term = collective_bytes_per_chip / ICI bytes/s per link

with the peaks of the target chip, looked up by `device_kind` in `PEAKS`.

FLOP/byte volumes come from the validated analytic cost model
(benchmarks/cost_model.py — see its docstring for why compiled cost_analysis
cannot be used directly: XLA counts while-loop bodies once, and reports
per-partition numbers).  The dry-run JSONs contribute the ground truth the
analytic model cannot know: per-device memory_analysis (capacity proof) and
the collective-op schedule (which collectives GSPMD actually emitted).

Headline metric per cell: MFU for compute-bound cells, MBU (memory-bandwidth
utilization of useful bytes) for memory-bound ones — reported as
`roofline_frac` in EXPERIMENTS.md §Roofline / §Perf.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Dict, List, Optional

from repro.configs import get_arch
from repro.configs.base import ALL_SHAPES, ShapeConfig

from benchmarks import cost_model

#: Published per-chip peaks, keyed by `jax.devices()[i].device_kind`.
#: TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s of chip-to-chip interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": dict(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

#: The chip the production dry-run mesh describes (16x16 v5e pods).
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
  """Peak FLOP/s and bytes/s of one chip; an unknown kind is an error."""
  if device_kind not in PEAKS:
    raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                     f"known: {sorted(PEAKS)}")
  return PEAKS[device_kind]

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def useful_flops(cfg, shape: ShapeConfig) -> float:
  n_active = cfg.active_params()
  if shape.kind == "train":
    return 6.0 * n_active * shape.seq_len * shape.global_batch
  if shape.kind == "prefill":
    return 2.0 * n_active * shape.seq_len * shape.global_batch
  return 2.0 * n_active * shape.global_batch


def useful_bytes(cfg, shape: ShapeConfig) -> float:
  """Minimum HBM traffic a perfect implementation must move per decode step:
  params once + the *compressed* context (PQ when the arch supports it — the
  paper's compressed representation IS the achievable lower bound, so exact-KV
  baselines score < 1 against it)."""
  best = (dataclasses.replace(cfg, pq_enabled=True)
          if cfg.supports_pq else cfg)
  if shape.kind == "decode":
    return (cost_model.param_bytes(best)
            + cost_model.kv_cache_bytes(best, shape.global_batch,
                                        shape.seq_len))
  return cost_model.param_bytes(best)


def analyze_cell(arch: str, shape: ShapeConfig, chips: int = 256,
                 n_data: int = 16, n_model: int = 16,
                 pq: bool = True, dryrun_rec: Optional[dict] = None) -> dict:
  cfg = get_arch(arch)
  if not pq:
    cfg = dataclasses.replace(cfg, pq_enabled=False)
  costs = cost_model.cell_costs(cfg, shape, n_data, n_model)
  peak = peaks(TARGET_KIND)

  t_compute = costs["flops"] / (chips * peak["flops"])
  t_memory = costs["hbm_bytes"] / (chips * peak["hbm_bw"])
  t_collective = costs["collective_bytes_per_chip"] / peak["ici_bw"]
  terms = {"compute": t_compute, "memory": t_memory,
           "collective": t_collective}
  dominant = max(terms, key=terms.get)
  t_step = max(terms.values())

  uf = useful_flops(cfg, shape)
  ub = useful_bytes(cfg, shape)
  mfu = uf / (chips * peak["flops"] * t_step) if t_step else 0.0
  mbu = ub / (chips * peak["hbm_bw"] * t_step) if t_step else 0.0
  headline = mfu if dominant == "compute" else (
      mbu if dominant == "memory" else max(mfu, mbu))

  rec = {
      "arch": arch, "shape": shape.name, "kind": shape.kind,
      "chips": chips, "pq": bool(pq and cfg.supports_pq),
      "t_compute_s": t_compute, "t_memory_s": t_memory,
      "t_collective_s": t_collective, "dominant": dominant,
      "t_step_s": t_step,
      "model_flops": uf, "impl_flops": costs["flops"],
      "useful_flops_ratio": uf / costs["flops"] if costs["flops"] else 0.0,
      "mfu": mfu, "mbu": mbu, "roofline_frac": headline,
      "hbm_bytes": costs["hbm_bytes"],
      "collective_bytes_per_chip": costs["collective_bytes_per_chip"],
  }
  if dryrun_rec is not None:
    rec["mem_analysis"] = dryrun_rec.get("memory", {})
    rec["collective_ops_observed"] = dryrun_rec.get(
        "collectives", {}).get("counts", {})
  return rec


def load_dryrun(results_dir: str = RESULTS_DIR) -> Dict[str, dict]:
  out = {}
  for path in glob.glob(os.path.join(results_dir, "*.json")):
    with open(path) as f:
      rec = json.load(f)
    key = (rec["arch"], rec["shape"], rec["mesh"],
           "pq" if rec.get("pq") else "nopq")
    out[key] = rec
  return out


def full_table(pq: bool = True) -> List[dict]:
  """All 40 (arch x shape) single-pod cells."""
  from repro.configs import ARCHS
  dryrun = load_dryrun()
  rows = []
  for arch in ARCHS:
    for shape in ALL_SHAPES:
      rec = dryrun.get((arch, shape.name, "16x16", "pq" if pq else "nopq"))
      rows.append(analyze_cell(arch, shape, pq=pq, dryrun_rec=rec))
  return rows


def format_table(rows: List[dict]) -> str:
  hdr = (f"{'arch':22s} {'shape':12s} {'pq':3s} "
         f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>9s} "
         f"{'dominant':>10s} {'MFU%':>6s} {'MBU%':>6s} {'roofl%':>7s}")
  lines = [hdr, "-" * len(hdr)]
  for r in rows:
    lines.append(
        f"{r['arch']:22s} {r['shape']:12s} {str(r['pq'])[0]:3s} "
        f"{r['t_compute_s']:10.5f} {r['t_memory_s']:10.5f} "
        f"{r['t_collective_s']:9.5f} {r['dominant']:>10s} "
        f"{100 * r['mfu']:6.1f} {100 * r['mbu']:6.1f} "
        f"{100 * r['roofline_frac']:7.1f}")
  return "\n".join(lines)


def run() -> list:
  from benchmarks import common
  lines = []
  for r in full_table(pq=True):
    lines.append(common.csv_line(
        f"roofline_{r['arch']}_{r['shape']}", 0.0,
        f"dominant={r['dominant']};compute_s={r['t_compute_s']:.5f};"
        f"memory_s={r['t_memory_s']:.5f};coll_s={r['t_collective_s']:.5f};"
        f"roofline_frac={r['roofline_frac']:.3f}"))
  return lines


if __name__ == "__main__":
  print(format_table(full_table(pq=True)))
  print("\n--- baseline (PQ off / exact KV) decode rows ---")
  rows = [r for r in full_table(pq=False) if r["kind"] == "decode"]
  print(format_table(rows))
