"""Small reusable timers shared by the serve driver and the benchmark harness.

`Stopwatch` wraps a block and (optionally) a `block_until_ready` target so
async-dispatched JAX work is actually counted; `time_us` is the classic
warmup-then-average microbenchmark loop.

`span(name, **attrs)` marks a region of host work twice: as a
`jax.profiler.TraceAnnotation`, so a profiler trace shows it on the host
plane on the same clock as the device's ops, and as a `Span` in a bounded,
process-wide ring (`spans()`, `dropped()`, `clear()`), stamped with
`time.perf_counter()`.  Recording is always on: two clock reads and one
append, no device sync.  Every program compile request (a fresh compile or
a persistent-cache load) is logged into the same ring as a zero-length
`compile` span.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, List, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

#: spans the ring holds before it drops its oldest
SPAN_CAPACITY = 2 ** 17
#: the name of the zero-length span logged per program compile request
COMPILE = "compile"
#: fires once per compile request: it wraps the persistent-cache lookup
#: and, on a miss, the backend compile
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
  """One finished span: perf_counter seconds, the innermost span open
  around it on the same thread (None at top level), and its attributes."""
  name: str
  t0: float
  t1: float
  parent: Optional[str]
  attrs: dict


class SpanLog:
  """Fixed-capacity ring of finished spans that counts what it dropped."""

  def __init__(self, capacity: int = SPAN_CAPACITY):
    self._ring: collections.deque = collections.deque(maxlen=capacity)
    self._added = 0

  def add(self, s: Span) -> None:
    self._added += 1
    self._ring.append(s)

  def snapshot(self) -> List[Span]:
    return list(self._ring)

  def dropped(self) -> int:
    return self._added - len(self._ring)

  def clear(self) -> None:
    self._ring.clear()
    self._added = 0


LOG = SpanLog()
_open = threading.local()


def _stack() -> list:
  st = getattr(_open, "names", None)
  if st is None:
    st = _open.names = []
  return st


class _OpenSpan:
  __slots__ = ("name", "attrs", "parent", "t0", "seconds", "_ann")

  def __init__(self, name: str, attrs: dict):
    self.name = name
    self.attrs = attrs
    self.seconds = 0.0

  def __enter__(self) -> "_OpenSpan":
    st = _stack()
    self.parent = st[-1] if st else None
    st.append(self.name)
    self._ann = TraceAnnotation(self.name, **self.attrs)
    self._ann.__enter__()
    self.t0 = time.perf_counter()
    return self

  def __exit__(self, *exc) -> bool:
    t1 = time.perf_counter()
    self._ann.__exit__(*exc)
    _stack().pop()
    self.seconds = t1 - self.t0
    LOG.add(Span(self.name, self.t0, t1, self.parent, self.attrs))
    return False


def span(name: str, **attrs) -> _OpenSpan:
  """Context manager recording `name` over the block; `.seconds` holds its
  duration after the block exits."""
  return _OpenSpan(name, attrs)


def spans() -> List[Span]:
  """The ring's spans, oldest first (a copy)."""
  return LOG.snapshot()


def dropped() -> int:
  """Spans the ring has dropped since the last `clear()`."""
  return LOG.dropped()


def clear() -> None:
  LOG.clear()


def _on_duration(event: str, duration: float, **kw) -> None:
  if event == _COMPILE_EVENT:
    t = time.perf_counter()
    st = _stack()
    LOG.add(Span(COMPILE, t, t, st[-1] if st else None,
                 dict(fun=kw.get("fun_name"), seconds=duration)))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


class Stopwatch:
  """Context manager measuring wall time of a block.

      with Stopwatch() as sw:
        out = fn(x)
        sw.wait_for(out)          # block on async dispatch before stopping
      print(sw.seconds)
  """

  def __init__(self):
    self.seconds = 0.0
    self._t0 = 0.0

  def __enter__(self) -> "Stopwatch":
    self._t0 = time.monotonic()
    return self

  def wait_for(self, tree) -> None:
    jax.block_until_ready(tree)

  def __exit__(self, *exc) -> bool:
    self.seconds = time.monotonic() - self._t0
    return False


def latency_percentiles_ms(step_seconds) -> dict:
  """Per-step latency percentiles over raw wall-clock samples (seconds).

  The one place the p50/p99 definition lives: the serve driver, the engine
  stats, and therefore the bench records + CI regression guard all report
  percentiles computed exactly the same way.
  """
  samples = list(step_seconds)
  if not samples:
    return dict(steps=0, p50_ms=None, p99_ms=None, mean_ms=None)
  import numpy as np
  a = np.asarray(samples, np.float64) * 1e3
  return dict(steps=int(a.size),
              p50_ms=round(float(np.percentile(a, 50)), 4),
              p99_ms=round(float(np.percentile(a, 99)), 4),
              mean_ms=round(float(a.mean()), 4))


def time_us(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
  """Average wall-clock microseconds per call (after warmup compiles)."""
  for _ in range(warmup):
    jax.block_until_ready(fn(*args))
  t0 = time.perf_counter()
  for _ in range(iters):
    jax.block_until_ready(fn(*args))
  return (time.perf_counter() - t0) / iters * 1e6
