"""Reduces a JAX profiler trace (`.xplane.pb`) to device busy time, time
per device operation, and the longest idle gaps, each gap attributed to
the `bench.*` host span that was open across it.

Device operations are the events of the `XLA Ops` line of each
`/device:TPU:<n>` plane.  On a v5e that line names each event by the text
of its HLO instruction (`%paged_flash_decode.15 = f32[...] custom-call(...)`);
an operation is named by the instruction's name alone, the part before
` = `, which for a Pallas kernel is the `name=` of its `pallas_call`.  A
loop's op (`while`) spans the ops of its body, so times per op overlap:
busy time is the union of the intervals.  A CPU run has no device plane;
there the operations are the host events that carry an `hlo_op` stat,
which lets the reduction be tested without a chip.  Device and host
events share the trace's clock.  Everything is clipped to the
`bench.window` span.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Tuple

from jax.profiler import ProfileData

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
  out: List[List[float]] = []
  for a, b in sorted(iv):
    if out and a <= out[-1][1]:
      out[-1][1] = max(out[-1][1], b)
    else:
      out.append([a, b])
  return [(a, b) for a, b in out]


def load(logdir: str) -> ProfileData:
  paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                    recursive=True)
  if not paths:
    raise FileNotFoundError(f"no .xplane.pb under {logdir}")
  return ProfileData.from_file(max(paths, key=os.path.getmtime))


def op_name(event_name: str) -> str:
  """`%name.3 = type op(...)` -> `name.3`; other names as they are."""
  return event_name.split(" = ", 1)[0].lstrip("%")


def _events(pd: ProfileData):
  """(device op events per device, host bench.* spans)."""
  devices: Dict[str, list] = {}
  spans: list = []
  cpu_ops: list = []
  for plane in pd.planes:
    if plane.name.startswith(DEVICE_PREFIX):
      for line in plane.lines:
        if line.name == OPS_LINE:
          devices[plane.name] = [(op_name(e.name), e.start_ns, e.end_ns)
                                 for e in line.events]
    elif plane.name == HOST_PLANE:
      for line in plane.lines:
        for e in line.events:
          if e.name.startswith(SPAN_PREFIX):
            spans.append((e.name, e.start_ns, e.end_ns))
          elif "hlo_op" in dict(e.stats):
            cpu_ops.append((e.name, e.start_ns, e.end_ns))
  if not devices and cpu_ops:
    devices["/host:CPU"] = cpu_ops
  return devices, spans


def _label(spans, a, b) -> str:
  """The innermost bench.* span open across the gap's midpoint."""
  mid = (a + b) / 2
  best = None
  for name, s, e in spans:
    if s <= mid <= e and name != WINDOW and (
        best is None or e - s < best[2] - best[1]):
      best = (name, s, e)
  return best[0] if best else "none"


def reduce(pd: ProfileData) -> dict:
  """`window_s`, `busy_s` (mean over devices), `ops` {name: seconds summed
  over devices}, `device_ops` and `idle_gaps` (the TOP longest, first
  device), all in seconds."""
  devices, spans = _events(pd)
  win = [(s, e) for n, s, e in spans if n == WINDOW]
  if not win or not devices:
    raise ValueError("trace holds no bench.window span or no device op")
  lo, hi = win[0]
  busy, ops = [], collections.Counter()
  gaps = []
  for i, name in enumerate(sorted(devices)):
    evs = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[name]
           if e > lo and s < hi]
    for n, s, e in evs:
      ops[n] += (e - s) * 1e-9
    merged = _union([(s, e) for _, s, e in evs])
    busy.append(sum(e - s for s, e in merged) * 1e-9)
    if i == 0:
      edges = [lo] + [x for iv in merged for x in iv] + [hi]
      gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
              if edges[j + 1] > edges[j]]
  gaps.sort(key=lambda g: g[0] - g[1])
  return dict(
      window_s=(hi - lo) * 1e-9,
      busy_s=sum(busy) / len(busy),
      devices=len(busy),
      ops=dict(ops),
      device_ops=[[n, s] for n, s in ops.most_common(TOP)],
      idle_gaps=[[_label(spans, a, b), (b - a) * 1e-9]
                 for a, b in gaps[:TOP]])


def kernel_seconds(reduced: dict, kernel: str) -> float:
  """Summed device time of the operations whose name holds `kernel`."""
  return sum(s for n, s in reduced["ops"].items() if kernel in n)
