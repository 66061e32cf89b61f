"""device_idle_pct: share of the traced window in which no operation ran on
the device, in % (`trace_reduce.reduce`: 1 - busy / window)."""


def read(run):
  if run.trace is None:
    return None
  return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
