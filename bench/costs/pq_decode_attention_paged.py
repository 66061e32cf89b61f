"""One call of the paged PQ decode kernel (`kernels/pq_decode.py`,
`pallas_call` name `pq_decode_attention_paged`): one layer, every live row.

A row that holds n tokens before the step holds n + 1 after it; of those,
b = clip(n + 1 - sink - recent, 0, body_capacity) are PQ codes.  Per row
and kv head, with g query heads per kv head, head size d, m subvectors
and K centroids:

  flops  2gKd (inner-product table) + gbm (score sums) + 2gb (softmax)
         + gbm (bucket sums) + 2gKd (bucket sums times value codebook)
  bytes  2bm x index bytes (key and value codes)
         + 2Kd x 2 (key and value codebooks, bf16)
         + 2gd (query, bf16) + 4gd + 8g (output and stats, f32)

The bytes are what the algorithm reads and writes in the storage format
the configuration states, however the kernel is written.
"""
from __future__ import annotations

import numpy as np

KERNEL = "pq_decode_attention_paged"


def body_tokens(lengths, shape: dict) -> np.ndarray:
  pq = shape["pq"]
  n = np.asarray(lengths, np.int64) + 1
  return np.clip(n - pq["sink"] - pq["recent"], 0, pq["body_capacity"])


def cost(lengths, shape: dict, window=None):
  """(flops, bytes) of one call over the live rows' `lengths`; a layer
  with a window is not counted (no PQ configuration has one yet)."""
  if window is not None:
    raise ValueError("the PQ kernel's count has no sliding window; a PQ "
                     "configuration with windowed layers is not supported")
  pq = shape["pq"]
  g, d, hkv = shape["h"] // shape["hkv"], shape["hd"], shape["hkv"]
  m, k = pq["m"], pq["k"]
  b = body_tokens(lengths, shape)
  rows = len(b) * hkv
  flops = hkv * float(np.sum(2 * g * b * m + 2 * g * b)) + rows * 4 * g * k * d
  nbytes = (hkv * float(np.sum(2 * b * m * pq["index_bytes"]))
            + rows * (2 * k * d * 2 + 2 * g * d + 4 * g * d + 8 * g))
  return flops, nbytes
