"""host_gap_ms: mean per engine step of the harness's `step()` wall time
minus the engine's decode time (`EngineStats.decode_step_s`, launch to
token sync) minus the step's prefill spans: the engine loop's own host
work."""


def read(run):
  steps = run.rec.steps
  if not steps:
    return None
  gap = [s.t1 - s.t0 - (s.decode_s or 0.0) - s.prefill_s for s in steps]
  return 1e3 * sum(gap) / len(gap)
