#!/usr/bin/env python3
"""Benchmark command: one cell of `BENCHMARK.json` on the chip.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's engine (`ServeEngine`, paged layout, paged scheduler)
with weights drawn from `--seed`, warms up the cell's one prefill shape and
one decode shape, serves the cell's traffic on the wall clock for
`--seconds`, and prints one JSON line last on stdout: `correct`,
`attempted`, `failed`, `metrics` (end-to-end with `--trace 0`, per-layer
with `--trace 1`), `device`, with `--trace 1` a `breakdown`, and last the
`checks`: each number compared beside its limit, which also close
stderr.  Exits non-zero with no line where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import os
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import ModuleType  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import check, drive, peaks, reference, spec, trace_reduce  # noqa: E402

#: HF config key -> program ModelConfig field, for every family; a family
#: adds its own (`HF_KEYS` of `families/<family>.py`)
HF_KEYS = dict(hidden_size="d_model", num_attention_heads="n_heads",
               num_key_value_heads="n_kv_heads", head_dim="head_dim",
               num_hidden_layers="n_layers", vocab_size="vocab_size",
               rope_theta="rope_theta", rms_norm_eps="norm_eps")


def _process_age() -> float:
  """Seconds since this process started (Linux), else since import."""
  try:
    with open("/proc/self/stat") as f:
      start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
      up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")
  except (OSError, ValueError, IndexError):
    return time.perf_counter() - T_PROCESS


def model_config(conf: dict, family):
  from repro.configs import get_arch
  hf = conf["hf_config"]
  keys = {**HF_KEYS, **family.HF_KEYS}
  over = {v: hf[k] for k, v in keys.items() if hf.get(k) is not None}
  over["dtype_str"] = hf.get("torch_dtype", "bfloat16")
  over.update(conf["serving"])
  return dataclasses.replace(get_arch(conf["arch"]), **over)


def shape(conf: dict, family, context_len: int) -> dict:
  """Sizes the cost functions take, from the configuration file."""
  out = dict(family.dims(conf["hf_config"]), pq=None)
  out["layer_weight_flops"] = family.layer_weight_flops(out)
  pq = reference.pq_spec(conf["serving"], context_len)
  if pq is not None:
    sink, recent, m, k, _, nb = pq
    # codes are stored in the narrowest integer that holds K
    out["pq"] = dict(m=m, k=k, sink=sink, recent=recent, body_capacity=nb,
                     index_bytes=1 if k <= 256 else 2)
  return out


def _check_tree(weights, model) -> None:
  want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
  got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
  exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
  if got != exp:
    raise ValueError("benchmark weights do not match the program's "
                     f"parameter tree: {got} != {exp}")


@dataclasses.dataclass
class Run:
  """What the metric readers read (`metrics/<name>.py`)."""
  window: drive.Window
  rec: drive.Recorder
  setup_s: float
  trace: dict | None
  shape: dict
  peaks: dict | None              # None off the chip


def _device(devices) -> dict:
  peak = 0
  for d in devices:
    st = d.memory_stats() or {}
    peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
  return dict(platform=devices[0].platform, kind=devices[0].device_kind,
              count=len(devices), memory_peak_bytes=peak)


def _setup(rec: drive.Recorder, traffic) -> None:
  """Warm up the cell's one prefill shape and one decode shape: the
  sessions are prefilled here and decode twice."""
  for r in traffic.initial:
    rec.submit(r)
  while rec.engine.queue_view:
    rec.step()
  rec.step()
  rec.steps.clear()
  rec.prefills.clear()
  rec.decodes.clear()
  rec.engine.reset_stats()


@dataclasses.dataclass
class Outcome:
  """A served window and what the check needs after it."""
  run: Run
  device: dict
  failed: int
  attempted: int
  weights: dict
  hf: dict
  context_len: int
  family: ModuleType              # families/<family>.py of the configuration
  pq: tuple | None                # reference.pq_spec of the configuration


def serve(cell: dict, seed: int, seconds: float, trace: bool,
          setup_start: float) -> Outcome:
  """Build, warm up and serve the cell's traffic for `seconds`; the engine
  is freed before returning, the weights are kept for the reference.

  `setup_start` is the set-up's start on `time.perf_counter()`."""
  from repro.launch.engine import ServeEngine
  from repro.models import Model

  conf, wl = cell["config"], cell["workload"]
  hf, sizes = conf["hf_config"], wl["engine"]
  family = spec.family(conf["family"])
  cfg = model_config(conf, family)
  weights = family.make(hf, seed, cfg.dtype)
  _check_tree(weights, Model(cfg, context_len=sizes["context_len"]))
  engine = ServeEngine(cfg, params=weights, seed=seed, **sizes)
  traffic = spec.traffic_kind(wl["traffic"]).generate(
      wl["params"], seed, cfg.vocab_size, sizes, seconds)
  rec = drive.Recorder(engine)
  _setup(rec, traffic)
  gc.collect()
  gc.freeze()
  setup_s = time.perf_counter() - setup_start

  logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
  if trace:
    jax.profiler.start_trace(logdir)
  try:
    window = drive.run_window(rec, traffic, seconds)
  finally:
    if trace:
      jax.profiler.stop_trace()
  gc.unfreeze()
  device = _device(jax.devices())
  failed = engine.stats.preempts + sum(
      1 for s in rec.served.values() if s.handle.failed or s.handle.shed)
  del engine
  rec.engine = None
  gc.collect()

  reduced = None
  if trace:
    try:
      reduced = trace_reduce.reduce(trace_reduce.load(logdir))
    finally:
      shutil.rmtree(logdir, ignore_errors=True)
    device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
  # device metrics need the chip's peaks; off the chip they stay unread
  chip = peaks.peaks(device["kind"]) if device["platform"] == "tpu" else None
  run = Run(window, rec, setup_s, reduced,
            shape(conf, family, sizes["context_len"]), chip)
  return Outcome(run, device, failed, window.attempted, weights, hf,
                 sizes["context_len"], family,
                 reference.pq_spec(conf["serving"], sizes["context_len"]))


def run_cell(name: str, cell: dict, metric_specs: list, seed: int,
             seconds: float, trace: bool, setup_start: float) -> dict:
  """Serve, read the metrics, check against the reference; returns the
  result line as a dict."""
  del name
  o = serve(cell, seed, seconds, trace, setup_start)
  metrics = {}
  for m in metric_specs:
    v = spec.metric_reader(m["name"]).read(o.run)
    if v is not None:
      metrics[m["name"]] = dict(value=float(v), unit=m["unit"])

  lim = cell["workload"]["check"]
  picked = check.sample(list(o.run.rec.served.values()), lim["sample"], seed)
  gaps = check.gaps(o.weights, o.hf, o.family, picked, o.context_len,
                    pq=o.pq)
  compared = int(sum(len(g) for g in gaps))
  read = check.readings(gaps)
  limits = lim["limits"]
  correct = bool(compared > 0 and o.failed == 0 and limits and all(
      limits[k] is not None and read[k] is not None and read[k] <= limits[k]
      for k in limits))
  out = dict(correct=correct, attempted=o.attempted, failed=o.failed,
             metrics=metrics, device=o.device)
  if o.run.trace is not None:
    out["breakdown"] = dict(device_ops=o.run.trace["device_ops"],
                            idle_gaps=o.run.trace["idle_gaps"])
  w, rec = o.run.window, o.run.rec
  out["records"] = dict(
      window_s=w.t1 - w.t0, steps=len(rec.steps),
      decode_steps=len(rec.decodes), prefills=len(rec.prefills),
      sampled=[[s.handle.prompt_len, len(s.handle.tokens)] for s in picked],
      readings=read)
  out["checks"] = {k: dict(value=read[k], limit=limits[k]) for k in limits}
  out["checks"]["tokens_compared"] = dict(value=compared, limit=1)
  return out


def emit(out: dict) -> None:
  """Result line last on stdout; the compared numbers last on stderr."""
  checks = out.pop("checks")
  out["checks"] = checks
  for k, c in checks.items():
    print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
  print(json.dumps(out), flush=True)


def main(argv=None) -> int:
  p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
  p.add_argument("--workload", required=True)
  p.add_argument("--seed", type=int, required=True)
  p.add_argument("--seconds", type=float, required=True)
  p.add_argument("--trace", type=int, choices=(0, 1), default=0)
  args = p.parse_args(argv)
  setup_start = time.perf_counter() - _process_age()
  man = spec.manifest()
  cell = spec.cell(args.workload, man)
  devices = jax.devices()
  need = cell["entry"]["chips"]
  if devices[0].platform != "tpu" or len(devices) < need:
    print(f"needs {need} TPU chip(s); JAX finds {len(devices)} "
          f"{devices[0].platform} device(s)", file=sys.stderr)
    return 2
  from repro.common import compile_cache
  compile_cache.enable()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
  out = run_cell(args.workload, cell,
                 spec.metrics_for(args.workload, bool(args.trace), man),
                 args.seed, args.seconds, bool(args.trace), setup_start)
  emit(out)
  return 0


if __name__ == "__main__":
  sys.exit(main())
