"""decode_tok_s: output tokens produced in the window over its seconds."""


def read(run):
  t0, t1 = run.window.t0, run.window.t1
  n = sum(1 for s in run.rec.served.values() for t in s.times
          if t0 <= t <= t1)
  return n / (t1 - t0) if n else None
