"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract memory / cost / collective statistics for the roofline analysis.

MUST be the process entry point (python -m repro.launch.dryrun ...): the first
two lines below pin 512 placeholder devices BEFORE any jax import, because jax
locks the device count on first init.  Nothing else in the repo sets XLA_FLAGS.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402  (imports must follow the env pin)
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax

from repro.configs import ARCHS, get_arch
from repro.configs.base import ALL_SHAPES, ShapeConfig, smoke_shape
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
  n = 1
  for d in dims.split(","):
    if d:
      n *= int(d)
  return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
  """Sum output-shape bytes of every collective op in optimized HLO.

  `-start` ops are counted, `-done` skipped (async pairs).  Tuple outputs
  contribute each element.
  """
  totals = {op: 0 for op in COLLECTIVE_OPS}
  counts = {op: 0 for op in COLLECTIVE_OPS}
  for line in hlo_text.splitlines():
    stripped = line.strip()
    m = re.match(r"^(%?[\w.\-]+)\s*=\s*(.*)$", stripped)
    if not m:
      continue
    rhs = m.group(2)
    for op in COLLECTIVE_OPS:
      # match "<shape(s)> <op>(" or "<shape(s)> <op>-start("
      opm = re.search(r"^\(?([^)]*?)\)?\s+" + re.escape(op)
                      + r"(-start)?\(", rhs)
      if opm and f" {op}-done(" not in rhs:
        shapes = _SHAPE_RE.findall(opm.group(1))
        b = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        totals[op] += b
        counts[op] += 1
        break
  totals_all = sum(totals.values())
  return {"by_op": totals, "counts": counts, "total_bytes": totals_all}


def run_cell(arch: str, shape: ShapeConfig, multi_pod: bool,
             pq: bool = True, reduced: bool = False,
             print_analysis: bool = True,
             overrides: dict | None = None) -> dict:
  """Lower + compile one cell; return the roofline record."""
  cfg = get_arch(arch, reduced=reduced)
  if not pq:
    cfg = dataclasses.replace(cfg, pq_enabled=False)
  if overrides:
    cfg = dataclasses.replace(cfg, **overrides)
  mesh = make_production_mesh(multi_pod=multi_pod)

  rec = {
      "arch": arch, "shape": shape.name, "kind": shape.kind,
      "overrides": dict(overrides or {}),
      "mesh": "2x16x16" if multi_pod else "16x16",
      "chips": int(mesh.size), "pq": pq and cfg.supports_pq,
      "seq_len": shape.seq_len, "global_batch": shape.global_batch,
  }
  t0 = time.monotonic()
  with mesh:
    progs = steps_lib.build_programs(cfg, shape, mesh, donate=False)
    lowered = progs.fn.lower(*progs.abstract_inputs)
    rec["lower_s"] = round(time.monotonic() - t0, 2)
    t1 = time.monotonic()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.monotonic() - t1, 2)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        k: int(getattr(mem, k))
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)}
    cost = compiled.cost_analysis()
    rec["cost"] = {k: float(v) for k, v in cost.items()
                   if isinstance(v, (int, float))}
    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["hlo_lines"] = hlo.count("\n")

    if print_analysis:
      print(f"--- {arch} x {shape.name} x {rec['mesh']} "
            f"(pq={rec['pq']}) ---")
      print("memory_analysis:", rec["memory"])
      print("cost_analysis flops=%.3e bytes=%.3e" % (
          rec["cost"].get("flops", 0.0),
          rec["cost"].get("bytes accessed", 0.0)))
      print("collectives:", rec["collectives"]["by_op"],
            "total=%.3e" % rec["collectives"]["total_bytes"])
  return rec


def shape_by_name(name: str) -> ShapeConfig:
  for s in ALL_SHAPES:
    if s.name == name:
      return s
  if name.startswith("smoke"):
    return smoke_shape(name.split("_")[1] if "_" in name else "train")
  raise KeyError(name)


def main():
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--arch", default="all",
                  help="arch id or 'all'")
  ap.add_argument("--shape", default="all",
                  help="shape name or 'all'")
  ap.add_argument("--mesh", default="single",
                  choices=["single", "multi", "both"])
  ap.add_argument("--no-pq", action="store_true",
                  help="baseline: exact (uncompressed) KV cache")
  ap.add_argument("--reduced", action="store_true",
                  help="smoke-scale configs (plumbing check)")
  ap.add_argument("--out", default="benchmarks/results/dryrun",
                  help="directory for per-cell JSON records")
  ap.add_argument("--set", action="append", default=[],
                  help="config override key=value (e.g. weight_quant=int8, "
                       "pq_k=256, parallel_block=true) — for Perf variants")
  ap.add_argument("--tag", default="", help="suffix for output JSON names")
  args = ap.parse_args()

  overrides = {}
  for kv in args.set:
    k, v = kv.split("=", 1)
    if v.lower() in ("true", "false"):
      overrides[k] = v.lower() == "true"
    else:
      try:
        overrides[k] = int(v)
      except ValueError:
        overrides[k] = v

  archs = list(ARCHS) if args.arch == "all" else [args.arch]
  shapes = (list(ALL_SHAPES) if args.shape == "all"
            else [shape_by_name(args.shape)])
  meshes = {"single": [False], "multi": [True],
            "both": [False, True]}[args.mesh]

  os.makedirs(args.out, exist_ok=True)
  failures = []
  for arch in archs:
    for shape in shapes:
      for multi in meshes:
        tag = f"{arch}__{shape.name}__{'multi' if multi else 'single'}" \
              + ("__nopq" if args.no_pq else "") \
              + (f"__{args.tag}" if args.tag else "")
        out_path = os.path.join(args.out, tag + ".json")
        try:
          rec = run_cell(arch, shape, multi, pq=not args.no_pq,
                         reduced=args.reduced, overrides=overrides)
          with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
          print(f"[ok] {tag}  lower={rec['lower_s']}s "
                f"compile={rec['compile_s']}s")
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
          traceback.print_exc()
          failures.append((tag, repr(e)))
          print(f"[FAIL] {tag}: {e}")
  if failures:
    print(f"\n{len(failures)} FAILURES:")
    for tag, err in failures:
      print(" ", tag, err[:200])
    raise SystemExit(1)
  print("\nall cells passed")


if __name__ == "__main__":
  main()
