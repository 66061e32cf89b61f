"""On-chip benchmark of the AQPIM serve path.

Everything that belongs to one model configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name `BENCHMARK.json`
gives it:

  configs/<config>.json       published widths, the cuts, the serve
                              settings, and the model family it names
  families/<family>.py        a model family's weights, reference block and
                              per-layer weight FLOPs
  workloads/<cell>.json       engine sizes, traffic kind and its parameters,
                              correctness sample and limit
  traffic/<kind>.py           a generator for one kind of traffic
  metrics/<metric>.py         a reader that takes one metric from a run
  costs/<kernel>.py           a kernel's operations and bytes at real lengths

`run.py` is the command; `control.py` reads the correctness numbers of the
program and of its lower-precision control for a set of seeds.
"""
