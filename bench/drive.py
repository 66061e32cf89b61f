"""Drives `ServeEngine.submit` / `ServeEngine.step` on the wall clock and
records what a client and a profiler would see.

Spans, on the host clock and as `jax.profiler.TraceAnnotation`s so that
they line up with the device trace:

  bench.window   the measured window
  bench.step     one `ServeEngine.step()` call
  bench.prefill  one admission's prefill (`ServeEngine._prefill_into`,
                 wrapped on the instance); it ends when the first token is
                 on the host, which is when the harness stamps it
  bench.decode   the dispatch of one batched decode (`layout.decode`)
  bench.host     the harness's own work between steps

Decode tokens are stamped when `step()` returns them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

now = time.perf_counter


@dataclasses.dataclass
class Served:
  """One request as its client saw it."""
  handle: object                     # the engine's RequestHandle
  times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Step:
  t0: float
  t1: float
  decode_s: Optional[float]          # EngineStats.decode_step_s sample
  prefill_s: float                   # summed bench.prefill spans


class Recorder:
  """Wraps the engine's prefill and decode on the instance and keeps the
  client-side record of every request."""

  def __init__(self, engine):
    self.engine = engine
    self.served: Dict[int, Served] = {}
    self.prefills: List[tuple] = []  # (rid, t0, t1)
    self.decodes: List[np.ndarray] = []  # active rows' cached lengths
    self.steps: List[Step] = []
    self._first: Dict[int, float] = {}
    self._prefill_into = engine._prefill_into
    self._decode = engine.layout.decode
    engine._prefill_into = self._wrap_prefill
    engine.layout.decode = self._wrap_decode

  def _wrap_prefill(self, slot, req, plan):
    with TraceAnnotation("bench.prefill"):
      t0 = now()
      first = self._prefill_into(slot, req, plan)
      t1 = now()
    self.prefills.append((req.rid, t0, t1))
    self._first[req.rid] = t1
    return first

  def _wrap_decode(self, params, cur, lengths):
    live = [s for s, r in enumerate(self.engine._slots) if r is not None]
    self.decodes.append(np.asarray(lengths)[live].copy())
    with TraceAnnotation("bench.decode"):
      return self._decode(params, cur, lengths)

  def submit(self, req) -> Served:
    h = self.engine.submit(req.prompt, req.max_new_tokens)
    s = self.served[h.rid] = Served(h)
    return s

  def step(self) -> list:
    stats = self.engine.stats
    n_dec, n_pre = stats.decode_steps, len(self.prefills)
    with TraceAnnotation("bench.step"):
      t0 = now()
      finished = self.engine.step()
      t1 = now()
    dec = stats.decode_step_s[-1] if stats.decode_steps > n_dec else None
    pre = sum(b - a for _, a, b in self.prefills[n_pre:])
    self.steps.append(Step(t0, t1, dec, pre))
    with TraceAnnotation("bench.host"):
      for s in self.served.values():
        h = s.handle
        new = len(h.tokens) - len(s.times)
        if new > 0 and not s.times:
          s.times.append(self._first.get(h.rid, t1))
          new -= 1
        s.times.extend([t1] * new)
    return finished


@dataclasses.dataclass
class Window:
  t0: float
  t1: float
  attempted: int                     # requests live in the window


def run_window(rec: Recorder, traffic, seconds: float) -> Window:
  """Serve the sessions for `seconds` of wall clock, replacing each that
  finishes with the next refill; a step already begun runs to its end, so
  the window closes at the end of the last step."""
  refills = list(traffic.refills)
  attempted = sum(1 for s in rec.served.values() if not s.handle.done)
  with TraceAnnotation("bench.window"):
    t0 = now()
    end = t0 + seconds
    while now() < end and rec.engine.has_work:
      for _ in rec.step():
        if refills:
          rec.submit(refills.pop(0))
          attempted += 1
    t1 = now()
  return Window(t0, t1, attempted)
