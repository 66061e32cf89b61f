"""Kernel operation and byte counts against hand arithmetic at a small
shape (the step's weight count from the dense family), with and without
windowed layers, and the peak table's refusal of an unknown chip."""
import pathlib
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import peaks, spec, stats  # noqa: E402
from bench.costs import decode_step, paged_flash_decode  # noqa: E402
from bench.costs import pq_decode_attention_paged as pqk  # noqa: E402

SHAPE = dict(layers=2, d=16, h=4, hkv=2, hd=8, f=32, v=50, pq=None,
             windows=(None, None))
# the dense family's count, as `run.shape` adds it
SHAPE["layer_weight_flops"] = spec.family("dense").layer_weight_flops(SHAPE)
PQ = dict(SHAPE, pq=dict(m=2, k=4, sink=2, recent=3, body_capacity=16,
                         index_bytes=2))
# three layers with a window of 8, then one over the whole length
WIN = dict(SHAPE, layers=4, windows=(8, 8, 8, None))


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


def _paged_by_hand(keys):
  """(flops, bytes) of the paged kernel over rows attending `keys` keys;
  g = 2, d = 8, 2 kv heads."""
  fl = 2 * sum(4 * 2 * n * 8 + 2 * 2 * n for n in keys)
  by = 2 * sum(2 * n * 8 * 2 for n in keys) + 2 * len(keys) * (
      2 * 2 * 8 + 4 * 2 * 8)
  return fl, by


def test_windowed_counts():
  # rows hold 5, 20, 100 tokens -> attend 6, 21, 101; at a window of 8: 6, 8, 8
  lengths = [5, 20, 100]
  full, win = _paged_by_hand([6, 21, 101]), _paged_by_hand([6, 8, 8])
  assert paged_flash_decode.cost(lengths, WIN) == full
  assert paged_flash_decode.cost(lengths, WIN, 8) == win
  per_layer = 16 * 8 * (4 + 2 * 2) + 4 * 8 * 16 + 3 * 16 * 32
  dense = 3 * 2.0 * (4 * per_layer + 16 * 50)
  assert decode_step.flops(lengths, WIN) == dense + 3 * win[0] + full[0]


def test_roofline_share_sums_the_window_groups():
  decodes = [np.array([5, 20, 100]), np.array([6, 1000])]
  run = SimpleNamespace(
      trace=dict(ops={"paged_flash_decode.7": 2e-6}, device_ops=[]),
      peaks=dict(bf16_flops=197e12, hbm_bytes_per_s=819e9),
      rec=SimpleNamespace(decodes=decodes), shape=WIN)

  def least(window):
    return sum(max(fl / 197e12, by / 819e9) for fl, by in (
        paged_flash_decode.cost(ln, WIN, window) for ln in decodes))

  share = stats.roofline_share(run, paged_flash_decode)
  assert share == pytest.approx(100 * (3 * least(8) + least(None)) / 2e-6,
                                rel=1e-12)
  assert share < 0.9 * 100 * 4 * least(None) / 2e-6


@pytest.mark.parametrize("count", [
    decode_step.flops,
    lambda lengths, shape: pqk.cost(lengths, shape, 8),
], ids=["decode_step", "pq_kernel"])
def test_pq_with_a_window_is_refused(count):
  with pytest.raises(ValueError, match="window"):
    count([9, 4], dict(PQ, windows=(8, None)))


def test_peaks_known_and_unknown():
  v5e = peaks.peaks("TPU v5 lite")
  assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
  with pytest.raises(ValueError):
    peaks.peaks("TPU v9 imaginary")
