"""step_mfu.decode: the whole decode step's share of the chip's bf16 peak,
in %: algorithmic FLOPs of the window's decode steps at the rows' real
lengths (`costs/decode_step.py`) over their summed decode time."""
from bench.costs import decode_step


def read(run):
  t = sum(s.decode_s for s in run.rec.steps if s.decode_s is not None)
  if run.peaks is None or not run.rec.decodes or t <= 0:
    return None
  fl = sum(decode_step.flops(ln, run.shape) for ln in run.rec.decodes)
  return 100.0 * fl / t / run.peaks["bf16_flops"]
