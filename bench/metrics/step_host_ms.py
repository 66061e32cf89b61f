"""step_host_ms: mean over the window's `engine.step` spans of the span's
duration minus its `decode.wait` child: all the host work the synchronous
loop puts between two device programs (the program's own spans,
`repro.common.timing`).  None where the program records no spans, where
its span log dropped entries, or with no step in the window."""
import bisect

from repro.common import timing


def read(run):
  if getattr(timing, "spans", None) is None or timing.dropped():
    return None
  t0, t1 = run.window.t0, run.window.t1
  inside = [s for s in timing.spans() if t0 <= s.t0 and s.t1 <= t1]
  steps = [s for s in inside if s.name == "engine.step"]
  if not steps:
    return None
  waits = sorted((s.t0, s.t1) for s in inside if s.name == "decode.wait")
  starts = [a for a, _ in waits]
  host = []
  for s in steps:
    i = bisect.bisect_left(starts, s.t0)
    blocked = 0.0
    while i < len(waits) and waits[i][1] <= s.t1:
      blocked += waits[i][1] - waits[i][0]
      i += 1
    host.append(s.t1 - s.t0 - blocked)
  return 1e3 * sum(host) / len(host)
