"""Operations of one batched decode step of the whole model, counted over
the live rows only (idle lanes do no useful work).

Per live row: 2 x (weights it multiplies: per layer the family's count,
`layer_weight_flops` in the shape (`run.shape`), plus the LM head), and
per layer the attention the cache policy needs at the row's real length:
the paged kernel's count (`pq_decode_attention_paged` or
`paged_flash_decode`) and, for PQ, the exact attention over the sink and
recent tokens.
"""
from __future__ import annotations

import numpy as np

from bench.costs import paged_flash_decode, pq_decode_attention_paged


def flops(lengths, shape: dict) -> float:
  d, h, hd = shape["d"], shape["h"], shape["hd"]
  rows = len(lengths)
  total = rows * 2.0 * (shape["layers"] * shape["layer_weight_flops"]
                        + d * shape["v"])
  if shape["pq"] is None:
    attn, _ = paged_flash_decode.cost(lengths, shape)
  else:
    attn, _ = pq_decode_attention_paged.cost(lengths, shape)
    pq = shape["pq"]
    exact = np.minimum(np.asarray(lengths, np.int64) + 1,
                       pq["sink"] + pq["recent"])
    attn += float(np.sum(4 * h * exact * hd))
  return total + shape["layers"] * attn
