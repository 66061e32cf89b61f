"""Operations of one batched decode step of the whole model, counted over
the live rows only (idle lanes do no useful work).

Per live row: 2 x (weights it multiplies: per layer the family's count,
`layer_weight_flops` in the shape (`run.shape`), plus the LM head), and
per layer the attention the cache policy needs at the row's real length:
the paged kernel's count (`pq_decode_attention_paged` or
`paged_flash_decode`, at the layer's window) and, for PQ, the exact
attention over the sink and recent tokens.
"""
from __future__ import annotations

import numpy as np

from bench.costs import layer_groups, paged_flash_decode
from bench.costs import pq_decode_attention_paged


def _attention(lengths, shape: dict, window) -> float:
  """Attention FLOPs of one layer with that `window` over the live rows."""
  if shape["pq"] is None:
    return paged_flash_decode.cost(lengths, shape, window)[0]
  attn, _ = pq_decode_attention_paged.cost(lengths, shape, window)
  pq = shape["pq"]
  exact = np.minimum(np.asarray(lengths, np.int64) + 1,
                     pq["sink"] + pq["recent"])
  return attn + float(np.sum(4 * shape["h"] * exact * shape["hd"]))


def flops(lengths, shape: dict) -> float:
  rows = len(lengths)
  total = rows * 2.0 * (shape["layers"] * shape["layer_weight_flops"]
                        + shape["d"] * shape["v"])
  return total + sum(n * _attention(lengths, shape, w)
                     for w, n in layer_groups(shape))
