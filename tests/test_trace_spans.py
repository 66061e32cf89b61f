"""Spans and the compile counter on the serve path (`repro.common.timing`):
how `ServeEngine.step` nests its spans, what they carry, the bounded ring,
compile events, and what a profiler trace shows by name."""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.common import timing
from repro.configs import get_arch
from repro.launch.engine import ServeEngine

STEP_SPANS = {"engine.step", "engine.admit", "engine.prefill",
              "engine.blocks", "engine.decode", "decode.launch",
              "decode.wait", "engine.commit"}


def _engine(**kw):
  cfg = dataclasses.replace(get_arch("tinyllama-1.1b", reduced=True),
                            cache_policy="exact", dtype_str="float32")
  return ServeEngine(cfg, context_len=64, max_batch=2, prompt_capacity=32,
                     cache_layout="paged", scheduler="paged", **kw)


def _submit_two(eng):
  return [eng.submit(list(range(1, 20)), max_new_tokens=6),
          eng.submit(list(range(3, 12)), max_new_tokens=6)]


@pytest.fixture(autouse=True)
def _fresh_log():
  timing.clear()
  yield
  timing.clear()


def _by_name(name):
  return [s for s in timing.spans() if s.name == name]


def _within(inner, outer):
  return outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_step_spans_nest():
  eng = _engine()
  _submit_two(eng)
  for _ in range(3):
    eng.step()
  steps = _by_name("engine.step")
  assert [s.attrs["step"] for s in steps] == [0, 1, 2]
  assert all(s.parent is None for s in steps)
  for name, parent in [("engine.admit", "engine.step"),
                       ("engine.blocks", "engine.step"),
                       ("engine.decode", "engine.step"),
                       ("engine.commit", "engine.step"),
                       ("decode.launch", "engine.decode"),
                       ("decode.wait", "engine.decode")]:
    got = _by_name(name)
    assert len(got) == 3, name
    outers = _by_name(parent)
    for i, s in enumerate(got):
      assert s.parent == parent, name
      assert _within(s, outers[i]), name
  for launch, wait in zip(_by_name("decode.launch"), _by_name("decode.wait")):
    assert launch.t1 <= wait.t0


def test_each_prefill_carries_its_rid():
  eng = _engine()
  handles = _submit_two(eng)
  eng.step()
  pre = _by_name("engine.prefill")
  assert sorted(s.attrs["rid"] for s in pre) == sorted(h.rid for h in handles)
  admit, = _by_name("engine.admit")
  assert all(s.parent == "engine.admit" and _within(s, admit) for s in pre)


def test_decode_step_sample_is_the_decode_span():
  eng = _engine()
  _submit_two(eng)
  for _ in range(4):
    eng.step()
  spans = [s.t1 - s.t0 for s in _by_name("engine.decode")]
  assert list(eng.stats.decode_step_s) == spans
  assert len(spans) == 4


def test_ring_drops_and_counts_past_capacity(monkeypatch):
  monkeypatch.setattr(timing, "LOG", timing.SpanLog(capacity=4))
  for i in range(6):
    with timing.span("s", i=i) as sp:
      pass
    assert sp.seconds >= 0.0
  assert [s.attrs["i"] for s in timing.spans()] == [2, 3, 4, 5]
  assert timing.dropped() == 2
  timing.clear()
  assert timing.spans() == [] and timing.dropped() == 0


def test_first_steps_compile_and_later_steps_do_not():
  eng = _engine()
  _submit_two(eng)
  timing.clear()                  # the weights' init compiled above
  eng.step()                      # prefill and decode programs compile here
  first = _by_name(timing.COMPILE)
  assert first and all(s.t0 == s.t1 for s in first)
  funs = {s.attrs["fun"] for s in first}
  assert "jit(prefill)" in funs and "jit(decode_fused)" in funs, funs
  step, = _by_name("engine.step")
  assert all(step.t0 <= s.t0 <= step.t1 for s in first)
  timing.clear()
  for _ in range(3):
    eng.step()                    # same shapes: nothing compiles
  assert len(_by_name("engine.step")) == 3
  assert _by_name(timing.COMPILE) == []


def test_profiler_trace_shows_spans_and_stable_program_names(tmp_path):
  eng = _engine()
  _submit_two(eng)
  jax.profiler.start_trace(str(tmp_path))
  try:
    for _ in range(2):
      eng.step()
  finally:
    jax.profiler.stop_trace()
  path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                    recursive=True)
  host = [p for p in ProfileData.from_file(path).planes
          if p.name == "/host:CPU"]
  names, modules = set(), set()
  for plane in host:
    for line in plane.lines:
      for e in line.events:
        names.add(e.name)
        mod = dict(e.stats).get("hlo_module")
        if mod is not None:
          modules.add(mod)
  assert STEP_SPANS <= names, STEP_SPANS - names
  assert "jit_prefill" in modules, modules
  assert any("decode" in m for m in modules), modules


def _op_names(compiled_text):
  return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_programs_carry_layer_scopes(program):
  eng = _engine()
  if program == "prefill":
    lowered = eng._prefill.lower(
        eng.params, jnp.zeros((1, eng.prompt_capacity), jnp.int32),
        jnp.asarray([5], jnp.int32))
  else:
    lay = eng.layout
    decode = lay._decode_native if lay.block_native else lay._decode_fused
    lowered = decode.lower(
        eng.params, jnp.zeros((2,), jnp.int32), lay.storage,
        jnp.asarray(lay.manager.tables), jnp.zeros((2,), jnp.int32))
  module = lowered.as_text().split(" ", 2)[1]
  assert module.startswith("@jit_") and "lambda" not in module, module
  names = _op_names(lowered.compile().as_text())
  top = f"jit({module[len('@jit_'):]})/"
  for scope in ("embed", "logits"):
    assert any(n.startswith(top + scope + "/") for n in names), scope
  for scope in ("qkv", "cache.exact", "mlp"):      # inside the layer scan
    assert any(n.startswith(top + "while/") and f"/{scope}/" in n
               for n in names), scope
