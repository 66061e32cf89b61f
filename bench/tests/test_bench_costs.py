"""Kernel operation and byte counts against hand arithmetic at a small
shape (the step's weight count from the dense family), and the peak
table's refusal of an unknown chip."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import peaks, spec  # noqa: E402
from bench.costs import decode_step, paged_flash_decode  # noqa: E402
from bench.costs import pq_decode_attention_paged as pqk  # noqa: E402

SHAPE = dict(layers=2, d=16, h=4, hkv=2, hd=8, f=32, v=50, pq=None)
# the dense family's count, as `run.shape` adds it
SHAPE["layer_weight_flops"] = spec.family("dense").layer_weight_flops(SHAPE)
PQ = dict(SHAPE, pq=dict(m=2, k=4, sink=2, recent=3, body_capacity=16,
                         index_bytes=2))


def test_paged_flash_counts():
  # rows hold 9 and 4 tokens -> attend over 10 and 5; g = 2, d = 8, 2 heads
  fl, by = paged_flash_decode.cost([9, 4], SHAPE)
  assert fl == 2 * ((4 * 2 * 10 * 8 + 2 * 2 * 10) + (4 * 2 * 5 * 8 + 2 * 2 * 5))
  assert by == 2 * (2 * 10 * 8 * 2 + 2 * 5 * 8 * 2) + 4 * (2 * 2 * 8 + 4 * 2 * 8)


def test_pq_counts():
  # 9 + 1 - 2 - 3 = 5 body tokens; 3 + 1 - 5 < 0 -> none; 40 clips to 16
  assert list(pqk.body_tokens([9, 3, 40], PQ)) == [5, 0, 16]
  fl, by = pqk.cost([9], PQ)
  g, m, k, d = 2, 2, 4, 8
  per_head_fl = 2 * g * 5 * m + 2 * g * 5 + 4 * g * k * d
  per_head_by = 2 * 5 * m * 2 + 2 * k * d * 2 + 2 * g * d + 4 * g * d + 8 * g
  assert fl == 2 * per_head_fl
  assert by == 2 * per_head_by


def test_decode_step_flops():
  lengths = [9, 4]
  per_layer = 16 * 8 * (4 + 2 * 2) + 4 * 8 * 16 + 3 * 16 * 32
  dense = 2 * 2.0 * (2 * per_layer + 16 * 50)
  attn, _ = paged_flash_decode.cost(lengths, SHAPE)
  assert decode_step.flops(lengths, SHAPE) == dense + 2 * attn
  pq_attn, _ = pqk.cost(lengths, PQ)
  exact = 4 * 4 * (5 + 5) * 8          # min(n + 1, sink + recent) = 5, 5
  assert decode_step.flops(lengths, PQ) == dense + 2 * (pq_attn + exact)


def test_peaks_known_and_unknown():
  v5e = peaks.peaks("TPU v5 lite")
  assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
  with pytest.raises(ValueError):
    peaks.peaks("TPU v9 imaginary")
