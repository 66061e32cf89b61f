"""One call of the paged exact decode kernel (`kernels/paged_flash_decode.py`,
`pallas_call` name `paged_flash_decode`): one layer, every live row.

A row that holds n tokens before the step attends over n + 1 after it.
Per row and kv head, with g query heads per kv head and head size d:

  flops  4g(n+1)d (scores and weighted values) + 2g(n+1) (softmax)
  bytes  2(n+1)d x 2 (keys and values, bf16) + 2gd (query, bf16)
         + 4gd (output, f32)
"""
from __future__ import annotations

import numpy as np

KERNEL = "paged_flash_decode"


def cost(lengths, shape: dict):
  """(flops, bytes) of one call over the live rows' `lengths`."""
  g, d, hkv = shape["h"] // shape["hkv"], shape["hd"], shape["hkv"]
  n = np.asarray(lengths, np.int64) + 1
  rows = len(n) * hkv
  flops = hkv * float(np.sum(4 * g * n * d + 2 * g * n))
  nbytes = hkv * float(np.sum(2 * n * d * 2)) + rows * (2 * g * d + 4 * g * d)
  return flops, nbytes
