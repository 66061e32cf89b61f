"""The readers of the program's own spans (`step_host_ms`,
`decode_launch_ms`, `window_compiles`): on a synthetic span log they clip
to the window and stay silent with no step or a log that dropped entries;
on a served CPU window they read what the engine recorded."""
import pathlib
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec  # noqa: E402
from repro.common import timing  # noqa: E402
from repro.common.timing import Span  # noqa: E402

NAMES = ["step_host_ms", "decode_launch_ms", "window_compiles"]


def _read(name, r):
  return spec.metric_reader(name).read(r)


def _run(t0, t1):
  return SimpleNamespace(window=SimpleNamespace(t0=t0, t1=t1))


def _step(log, t, launch, wait, host):
  """One engine step starting at `t`: host work, a launch, a wait, more host
  work; returns its end."""
  t_l = t + host
  t_w = t_l + launch
  end = t_w + wait + host
  for s in [Span("decode.launch", t_l, t_w, "engine.decode", {}),
            Span("decode.wait", t_w, t_w + wait, "engine.decode", {}),
            Span("engine.decode", t_l, t_w + wait, "engine.step", {}),
            Span("engine.step", t, end, None, {"step": 0})]:
    log.add(s)
  return end


@pytest.fixture
def log(monkeypatch):
  fresh = timing.SpanLog()
  monkeypatch.setattr(timing, "LOG", fresh)
  return fresh


def test_readers_clip_to_the_window(log):
  # a slow step and a compile before the window, two steps inside, a compile
  # inside, and a slow step after
  t = _step(log, 0.0, launch=0.5, wait=1.0, host=0.5)
  log.add(Span(timing.COMPILE, 2.5, 2.5, None, {}))
  t = _step(log, 10.0, launch=0.002, wait=0.060, host=0.001)
  t = _step(log, t, launch=0.004, wait=0.060, host=0.001)
  log.add(Span(timing.COMPILE, t - 0.001, t - 0.001, "engine.step", {}))
  _step(log, 20.0, launch=0.5, wait=1.0, host=0.5)
  r = _run(5.0, 15.0)
  # host time per step: 2 * host + launch, 4 ms and 6 ms
  assert _read("step_host_ms", r) == pytest.approx(1e3 * 0.005)
  assert _read("decode_launch_ms", r) == pytest.approx(1e3 * 0.003)
  assert _read("window_compiles", r) == 1


@pytest.mark.parametrize("name", NAMES)
def test_readers_are_silent_without_steps(log, name):
  log.add(Span("decode.launch", 1.0, 1.1, "engine.decode", {}))
  log.add(Span(timing.COMPILE, 1.2, 1.2, None, {}))
  assert _read(name, _run(0.0, 5.0)) is None
  _step(log, 10.0, launch=0.001, wait=0.01, host=0.001)
  assert _read(name, _run(0.0, 5.0)) is None       # the step lies outside
  assert _read(name, _run(0.0, 15.0)) is not None


@pytest.mark.parametrize("name", NAMES)
def test_readers_are_silent_when_the_log_dropped(monkeypatch, name):
  small = timing.SpanLog(capacity=4)
  monkeypatch.setattr(timing, "LOG", small)
  _step(small, 1.0, launch=0.001, wait=0.01, host=0.001)
  assert _read(name, _run(0.0, 5.0)) is not None
  _step(small, 2.0, launch=0.001, wait=0.01, host=0.001)
  assert timing.dropped() == 4
  assert _read(name, _run(0.0, 5.0)) is None


def test_readers_on_a_served_cpu_window():
  hf = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
            vocab_size=256, rope_theta=1e6, rms_norm_eps=1e-5,
            torch_dtype="float32")
  serving = dict(cache_policy="exact", cache_layout="paged",
                 scheduler="paged", kv_block_size=16, decode_kernel="auto")
  workload = dict(traffic="closed_sessions",
                  engine=dict(context_len=64, prompt_capacity=48,
                              max_batch=4),
                  params=dict(sessions=4, prompt_len=[40, 48], refills=8),
                  check=dict(sample=1, limits=dict(max_logit_gap=1e-3)))
  cell = dict(config=dict(arch="mistral-7b", family="dense", hf_config=hf,
                          serving=serving),
              workload=workload)
  timing.clear()
  o = run.serve(cell, 2**31 + 11, 1.0, False, time.perf_counter())
  got = {n: _read(n, o.run) for n in NAMES}
  assert got["window_compiles"] == 0, got
  assert 0 < got["decode_launch_ms"] < got["step_host_ms"], got
  # the inside view holds the outside one: the harness's gap excludes the
  # launch and the prefills, which the engine's host time includes
  assert got["step_host_ms"] >= _read("host_gap_ms", o.run)
  w = o.run.window
  inside = [s for s in timing.spans() if w.t0 <= s.t0 and s.t1 <= w.t1]
  steps = [s for s in inside if s.name == "engine.step"]
  assert len(steps) == len(o.run.rec.steps)
  waits = [s.t1 - s.t0 for s in inside if s.name == "decode.wait"]
  bench_step = sum(s.t1 - s.t0 for s in o.run.rec.steps) / len(steps)
  covered = got["step_host_ms"] + 1e3 * sum(waits) / len(steps)
  assert covered <= 1e3 * bench_step
