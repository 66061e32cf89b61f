"""Client-side arithmetic the end-to-end metric readers share."""
from __future__ import annotations

from typing import List

import numpy as np

from bench import trace_reduce


def percentile(values, q: float):
  """Linear-interpolation percentile; None for no samples."""
  return float(np.percentile(values, q)) if len(values) else None


def token_gaps(run) -> List[float]:
  """Seconds between consecutive tokens of one request, both in the window."""
  t0, t1 = run.window.t0, run.window.t1
  out = []
  for s in run.rec.served.values():
    ts = [t for t in s.times if t0 <= t <= t1]
    out += [b - a for a, b in zip(ts, ts[1:])]
  return out


def roofline_share(run, cost_module):
  """A kernel's share of its roofline over the window, in %: the least
  time the chip could take for the window's calls (per call, the larger of
  operations over peak FLOP/s and bytes over peak HBM bandwidth; one call
  per layer per decode step) over the kernel's summed device time in the
  trace.  None without a trace or off the chip.  On the chip a cell that
  lists the metric runs the kernel, so a trace without it is an error: the
  kernel was renamed or left the path, and its share cannot be read."""
  if run.trace is None or run.peaks is None:
    return None
  t = trace_reduce.kernel_seconds(run.trace, cost_module.KERNEL)
  if t <= 0:
    raise ValueError(
        f"the trace holds no device op named {cost_module.KERNEL!r}; "
        f"the busiest are {[n for n, _ in run.trace['device_ops']]}")
  least = 0.0
  for ln in run.rec.decodes:
    fl, by = cost_module.cost(ln, run.shape)
    least += max(fl / run.peaks["bf16_flops"],
                 by / run.peaks["hbm_bytes_per_s"])
  return 100.0 * least * run.shape["layers"] / t
