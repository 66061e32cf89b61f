"""decode_step_ms: mean of `EngineStats.decode_step_s` (decode launch to
the sync on its tokens) over the window's decode steps."""


def read(run):
  d = [s.decode_s for s in run.rec.steps if s.decode_s is not None]
  return 1e3 * sum(d) / len(d) if d else None
