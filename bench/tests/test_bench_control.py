"""The check's control and faults at a reduced size on the CPU.

The control, the float8 reference in the program's place, reads a widest
gap far above the float32 program's on every seed; and a full run of the
harness with its timed path broken underneath comes out not correct, for
each fault a served cell can have: a token altered where it is produced,
and a decode step that leaves the cache state unchanged."""
import io
import json
import pathlib
import sys
import time
from contextlib import redirect_stderr, redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, drive, reference, run, spec  # noqa: E402

HF = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
          num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
          vocab_size=256, rope_theta=5e6, rms_norm_eps=1e-5,
          torch_dtype="float32")
EXACT = dict(cache_policy="exact", cache_layout="paged", scheduler="paged",
             kv_block_size=16, decode_kernel="auto")
LIMIT = 1e-3     # float32 program against float32 reference reads 0
PQ = dict(EXACT, cache_policy="pq", pq_m=4, pq_k=16, pq_sink=4, pq_recent=8)
PQ_LIMIT = 0.5   # bf16 codebook storage at K=16: see test_bench_reference
CELL = dict(config=dict(arch="yi-34b", family="dense", hf_config=HF,
                        serving=EXACT),
            workload=dict(
                traffic="closed_sessions",
                engine=dict(context_len=128, prompt_capacity=96, max_batch=3),
                params=dict(sessions=3, prompt_len=[60, 96], refills=4),
                check=dict(sample=3, limits=dict(max_logit_gap=LIMIT))))


@pytest.mark.parametrize("serving,limit", [(EXACT, LIMIT), (PQ, PQ_LIMIT)])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_above_the_limit(seed, serving, limit):
  from repro.launch.engine import ServeEngine
  dense = spec.family("dense")
  cfg = run.model_config(dict(CELL["config"], serving=serving), dense)
  w = dense.make(HF, seed, cfg.dtype)
  eng = ServeEngine(cfg, params=w, context_len=128, prompt_capacity=96,
                    max_batch=2)
  rng = np.random.default_rng(seed)
  hs = [eng.submit(rng.integers(1, 256, size=p), 30) for p in (80, 50)]
  while eng.has_work:
    eng.step()
  served = [drive.Served(h) for h in hs]
  pq = reference.pq_spec(serving, 128)
  prog = check.gaps(w, HF, dense, served, 128, pq=pq)
  ctrl = check.gaps(w, HF, dense, served, 128, control=True, pq=pq)
  assert max(float(g.max()) for g in prog) <= limit
  assert max(float(g.max()) for g in ctrl) > 3 * limit


def _roll_tokens(orig):
  def decode(self, params, cur, lengths):
    return jnp.roll(orig(self, params, cur, lengths), 1, axis=-1)
  return decode


def _keep_state(orig):
  def decode(self, params, cur, lengths):
    before = jax.tree.map(jnp.copy, self.storage)   # decode donates it
    out = orig(self, params, cur, lengths)
    self.storage = before
    return out
  return decode


@pytest.mark.parametrize("fault", [None, _roll_tokens, _keep_state])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
  from repro.core import cache_layout
  if fault is not None:
    monkeypatch.setattr(cache_layout.PagedLayout, "decode",
                        fault(cache_layout.PagedLayout.decode))
  name = "yi-34b-exact.ctx4k-decode"
  out = run.run_cell(name, CELL, spec.metrics_for(name, False), 9, 1.0,
                     False, time.perf_counter())
  buf = io.StringIO()
  with redirect_stdout(buf), redirect_stderr(io.StringIO()):
    run.emit(out)
  line = json.loads(buf.getvalue().splitlines()[-1])
  assert line["correct"] is (fault is None), line["checks"]
