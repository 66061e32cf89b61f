"""window_compiles: program compile requests (fresh compiles or persistent-
cache loads; the program's `compile` events, `repro.common.timing`) inside
the window: 0 where set-up warmed every shape.  None where the program
records no such events, where its span log dropped entries, or with no
step in the window."""
from repro.common import timing


def read(run):
  if getattr(timing, "spans", None) is None or timing.dropped():
    return None
  t0, t1 = run.window.t0, run.window.t1
  inside = [s for s in timing.spans() if t0 <= s.t0 and s.t1 <= t1]
  if not any(s.name == "engine.step" for s in inside):
    return None
  return sum(1 for s in inside if s.name == timing.COMPILE)
