"""Operations and bytes of the work the algorithm needs, at the rows' real
lengths, one file per kernel or program.  Each takes `lengths`, the cached
token counts of the live rows before the step, and `shape`, the sizes
`run.py` derives from the configuration (`shape()` there); a kernel that
runs one layer also takes that layer's window (None: the whole length),
and the layers that share a window are counted once, times their number
(`layer_groups`)."""
from __future__ import annotations

import collections


def layer_groups(shape: dict) -> list:
  """(window, number of layers) for each window `shape["windows"]` holds,
  in the order of the layers."""
  return list(collections.Counter(shape["windows"]).items())
