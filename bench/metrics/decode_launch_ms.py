"""decode_launch_ms: mean duration of the window's `decode.launch` spans:
the host time to stage a decode step's inputs and enqueue its program (the
program's own spans, `repro.common.timing`).  None where the program
records no spans, where its span log dropped entries, or with no step in
the window."""
from repro.common import timing


def read(run):
  if getattr(timing, "spans", None) is None or timing.dropped():
    return None
  t0, t1 = run.window.t0, run.window.t1
  inside = [s for s in timing.spans() if t0 <= s.t0 and s.t1 <= t1]
  if not any(s.name == "engine.step" for s in inside):
    return None
  d = [s.t1 - s.t0 for s in inside if s.name == "decode.launch"]
  return 1e3 * sum(d) / len(d) if d else None
