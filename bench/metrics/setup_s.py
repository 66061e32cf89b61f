"""setup_s: seconds from process start to the window's start: JAX start-up,
weights, engine, compile or cache load, warm-up and the closed loop's
initial prefills."""


def read(run):
  return run.setup_s
