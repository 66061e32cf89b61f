"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16 and 16 GB of HBM at 819 GB/s per chip.
Copied from `benchmarks/roofline.py` (`PEAKS`), which keeps the same
numbers for the program's analytic roofline.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9),
}


def peaks(device_kind: str) -> dict:
  """Peaks of one chip; a kind that is not in the table is an error."""
  if device_kind not in PEAKS:
    raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                     f"known: {sorted(PEAKS)}")
  return PEAKS[device_kind]
