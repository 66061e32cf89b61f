"""Decides `correct`: the served tokens of a seeded sample of requests
against the float32 reference (`reference.py`, with the PQ cache's
semantics for a PQ configuration), after the window.

Each served token of the sample has a gap: how far the reference's logit
for it lies below the reference's best logit at that position (0 where the
program served the reference's own greedy choice).  Two numbers are read
from the gaps, the widest (`max_logit_gap`) and their mean
(`mean_logit_gap`); a cell's workload file names the ones it compares and
the limit of each.  The sample holds the request with the most tokens
(prompt and served) and `sample - 1` more drawn from the seed, among the
requests that were served at least one token.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench import reference


def sample(served, k: int, seed: int) -> list:
  cands = sorted((s for s in served if s.handle.tokens),
                 key=lambda s: s.handle.rid)
  if not cands:
    return []
  longest = max(cands, key=lambda s: s.handle.prompt_len
                + len(s.handle.tokens))
  rest = [s for s in cands if s is not longest]
  rng = np.random.default_rng([seed, 1])
  pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
  return [longest] + [rest[i] for i in sorted(pick)]


def readings(gaps: List[np.ndarray]) -> dict:
  """The numbers a limit can hold, from the sample's gaps (None for none)."""
  g = np.concatenate(gaps) if gaps else np.zeros((0,))
  if not len(g):
    return dict(max_logit_gap=None, mean_logit_gap=None)
  return dict(max_logit_gap=float(g.max()), mean_logit_gap=float(g.mean()))


def gaps(weights, hf: dict, family, picked: list, s_pad: int,
         control: bool = False, pq=None) -> List[np.ndarray]:
  """Per sampled request, the gap of each served token (or, with
  `control`, of each token the float8 reference would have served);
  `family` is the configuration's `families/<family>.py` module, `pq`
  `reference.pq_spec` of a PQ configuration."""
  return [reference.served_gaps(
      weights, hf, family, np.asarray(s.handle.prompt, np.int32),
      np.asarray(s.handle.tokens, np.int32), s_pad, control=control, pq=pq)
          for s in picked]
