"""Closed loop of long sessions that decode to their context limit.

`params`: `sessions` (how many decode at once), `prompt_len` [lo, hi] and
`refills` (how many stand ready to replace sessions that reach the limit).
Every session asks for as many tokens as its context leaves.  The first
`sessions` are prefilled during set-up; a refill is submitted when a
session finishes, and its prefill falls inside the window, as it would
in serving.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import Request, Traffic, prompt, spread


def generate(params: dict, seed: int, vocab_size: int, engine: dict,
             seconds: float) -> Traffic:
  del seconds
  rng = np.random.default_rng(seed)
  lo, hi = params["prompt_len"]
  ctx = engine["context_len"]

  def batch(n):
    lens = rng.permutation(spread(lo, hi, n))
    return [Request(prompt(rng, int(p), vocab_size), ctx - int(p))
            for p in lens]

  initial = batch(params["sessions"])
  return Traffic(initial=initial, refills=batch(params["refills"]))
