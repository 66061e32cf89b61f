"""The harness's inner loop on the CPU at a reduced size, for both cache
policies, traced and not: it serves, reads its metrics, checks against the
reference, and prints one last line with exactly the keys the driver
reads."""
import io
import json
import pathlib
import sys
import time
from contextlib import redirect_stderr, redirect_stdout

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402

HF = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
          num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
          vocab_size=256, rope_theta=1e6, rms_norm_eps=1e-5,
          torch_dtype="float32")
PQ = dict(cache_policy="pq", cache_layout="paged", scheduler="paged",
          kv_block_size=16, decode_kernel="auto", pq_m=4, pq_k=16,
          pq_sink=4, pq_recent=8)
EXACT = dict(cache_policy="exact", cache_layout="paged", scheduler="paged",
             kv_block_size=16, decode_kernel="auto")
CLOSED = dict(traffic="closed_sessions",
              engine=dict(context_len=128, prompt_capacity=96, max_batch=3),
              params=dict(sessions=3, prompt_len=[80, 96], refills=4),
              check=dict(sample=2, limits=dict(max_logit_gap=1e-2)))
# short sessions, so that refills are admitted and prefilled in the window
SHORT = dict(traffic="closed_sessions",
             engine=dict(context_len=64, prompt_capacity=48, max_batch=4),
             params=dict(sessions=4, prompt_len=[40, 48], refills=8),
             check=dict(sample=3, limits=dict(max_logit_gap=1e-3)))
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _specs(names):
  return [dict(name=n, unit="u") for n in names]


E2E = _specs(["decode_tok_s", "tbt_p95_ms", "setup_s"])
LAYER = _specs(["host_gap_ms", "decode_step_ms",
                "step_mfu.decode", "pq_decode_roofline",
                "paged_flash_roofline", "device_idle_pct"])


def _line(serving, workload, trace, seed=2**31 + 7):
  cell = dict(config=dict(arch="mistral-7b", family="dense", hf_config=HF,
                          serving=serving),
              workload=workload)
  out = run.run_cell("cell", cell, LAYER if trace else E2E, seed, 1.0, trace,
                     time.perf_counter())
  buf, err = io.StringIO(), io.StringIO()
  with redirect_stdout(buf), redirect_stderr(err):
    run.emit(out)
  line = json.loads(buf.getvalue().strip().splitlines()[-1])
  return line, err.getvalue()


ALL_E2E = {m["name"] for m in E2E}


@pytest.mark.parametrize("serving,workload,trace,expect", [
    (PQ, CLOSED, False, ALL_E2E),
    (EXACT, SHORT, False, ALL_E2E),
    # off the chip the readers that need its peaks stay silent
    (EXACT, SHORT, True, {"host_gap_ms", "decode_step_ms",
                          "device_idle_pct"}),
])
def test_inner_loop_prints_the_result_line(serving, workload, trace, expect):
  line, err = _line(serving, workload, trace)
  assert [k for k in line if k in KEYS + ["breakdown"]] == (
      KEYS[:5] + (["breakdown"] if trace else []) + ["checks"])
  assert list(line)[-1] == "checks"
  assert line["attempted"] > 0 and line["failed"] == 0
  assert line["checks"]["tokens_compared"]["value"] > 0
  assert line["device"]["platform"] == "cpu"
  assert err.strip().splitlines()[-1].startswith("check ")
  # float32 program against the float32 reference (with PQ semantics for
  # the PQ cell): 1e-2 leaves room for a k-means tie broken the other way
  assert line["correct"], line["checks"]
  assert set(line["metrics"]) == expect
  if trace:
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
