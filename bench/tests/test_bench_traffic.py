"""Traffic generators: a seed changes the order and the token ids, never
the set of sizes; the same seed gives the same traffic."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

SEEDS = (0, 7, 2**31 + 11, 2**33 + 5)


def _work(cell):
  # the workload file itself: the PQ cell is held out of BENCHMARK.json
  return spec.load_json(f"bench/workloads/{cell}.json")


def _shape(tr):
  return sorted((len(r.prompt), r.max_new_tokens)
                for r in tr.initial + tr.refills)


def test_closed_sessions_same_sizes_every_seed():
  wl = _work("mistral-7b-pq.ctx8k-decode")
  kind = spec.traffic_kind(wl["traffic"])
  trs = [kind.generate(wl["params"], s, 32000, wl["engine"], 40.0)
         for s in SEEDS]
  assert all(_shape(t) == _shape(trs[0]) for t in trs)
  t = trs[0]
  assert len(t.initial) == wl["params"]["sessions"]
  ctx = wl["engine"]["context_len"]
  lo, hi = wl["params"]["prompt_len"]
  for r in t.initial + t.refills:
    assert lo <= len(r.prompt) <= hi
    assert len(r.prompt) + r.max_new_tokens == ctx
    assert r.prompt.min() >= 1 and r.prompt.max() < 32000
  assert [len(r.prompt) for r in trs[1].initial] != [
      len(r.prompt) for r in trs[2].initial]


@pytest.mark.parametrize("cell", ["mistral-7b-pq.ctx8k-decode",
                                  "yi-34b-exact.ctx4k-decode"])
def test_closed_sessions_same_seed_same_traffic(cell):
  wl = _work(cell)
  kind = spec.traffic_kind(wl["traffic"])
  a, b = (kind.generate(wl["params"], 2**31 + 3, 32000, wl["engine"], 40.0)
          for _ in range(2))
  assert all(np.array_equal(x.prompt, y.prompt)
             and x.max_new_tokens == y.max_new_tokens
             for x, y in zip(a.initial + a.refills, b.initial + b.refills))
