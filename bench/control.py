#!/usr/bin/env python3
"""Reads the correctness number of the program and of its control, for a
set of seeds, in one process; not part of a benchmark run.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed it serves the cell's traffic at the cell's own sizes and
load for `--seconds`, draws the same sample `run.py` draws, and reads the
widest logit gap twice: of the tokens the program served, and of the
tokens the float8 reference (`reference.py`, `low=True`) puts first at the
same positions, and the mean gap of each.  A limit lies between the
largest program reading and the smallest control reading (`PERF.md`).  One
JSON line per seed, then a summary line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1]),
                str(pathlib.Path(__file__).resolve().parents[1] / "src")]

import jax  # noqa: E402

from bench import check, run, spec  # noqa: E402


def read(cell: dict, seed: int, seconds: float) -> dict:
  o = run.serve(cell, seed, seconds, False, time.perf_counter())
  picked = check.sample(list(o.run.rec.served.values()),
                        cell["workload"]["check"]["sample"], seed)
  t = time.perf_counter()
  prog = check.gaps(o.weights, o.hf, o.family, picked, o.context_len,
                    pq=o.pq)
  t_ref = time.perf_counter() - t
  ctrl = check.gaps(o.weights, o.hf, o.family, picked, o.context_len,
                    control=True, pq=o.pq)
  return dict(seed=seed, program=check.readings(prog),
              control=check.readings(ctrl),
              program_each=[float(g.max()) for g in prog],
              control_each=[float(g.max()) for g in ctrl],
              tokens=int(sum(len(g) for g in prog)), reference_s=t_ref,
              failed=o.failed, setup_s=o.run.setup_s)


def main(argv=None) -> int:
  p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
  p.add_argument("--workload", required=True)
  p.add_argument("--seeds", required=True)
  p.add_argument("--seconds", type=float, required=True)
  args = p.parse_args(argv)
  if jax.devices()[0].platform != "tpu":
    print("control readings are taken on the chip", file=sys.stderr)
    return 2
  from repro.common import compile_cache
  compile_cache.enable()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
  cell = spec.cell(args.workload)
  rows = []
  for seed in (int(s) for s in args.seeds.split(",")):
    rows.append(read(cell, seed, args.seconds))
    print(json.dumps(rows[-1]), flush=True)
  print(json.dumps(dict(workload=args.workload, **{
      k: dict(lower=max(r["program"][k] for r in rows),
              upper=min(r["control"][k] for r in rows))
      for k in rows[0]["program"]})), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
