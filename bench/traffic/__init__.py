"""What a traffic generator hands the driver, and the arithmetic the kinds
share.

Every seed gets the same set of sizes, in another order, with other token
ids: the seed changes which request is which, not how
much work a run holds, so runs of different seeds spread no wider than
runs of one seed.  A kind lives in `traffic/<kind>.py` and exposes
`generate(params, seed, vocab_size, engine, seconds) -> Traffic`.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
  prompt: np.ndarray          # (prompt_len,) int32 token ids
  max_new_tokens: int


@dataclasses.dataclass
class Traffic:
  initial: List[Request]      # submitted and prefilled during set-up
  refills: List[Request]      # one submitted per finished one, in the window


def spread(lo: int, hi: int, n: int) -> np.ndarray:
  """n integers at the midpoint quantiles of uniform [lo, hi]."""
  u = (np.arange(n) + 0.5) / n
  return np.rint(lo + u * (hi - lo)).astype(np.int64)


def prompt(rng: np.random.Generator, n: int, vocab_size: int) -> np.ndarray:
  return rng.integers(1, vocab_size, size=n).astype(np.int32)
