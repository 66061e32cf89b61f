"""trace_reduce on a trace this test records on the CPU: the window, the
device-busy union, time per operation and the gaps' host spans."""
import pathlib
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import stats, trace_reduce  # noqa: E402
from bench.costs import paged_flash_decode  # noqa: E402


def test_reduce_reads_window_busy_ops_and_idle_spans(tmp_path):
  f = jax.jit(lambda x: jnp.tanh(x @ x))
  x = jnp.ones((384, 384), jnp.float32)
  f(x).block_until_ready()
  jax.profiler.start_trace(str(tmp_path))
  with TraceAnnotation("bench.window"):
    for _ in range(3):
      with TraceAnnotation("bench.step"):
        f(x).block_until_ready()
      with TraceAnnotation("bench.host"):
        time.sleep(0.05)
  jax.profiler.stop_trace()

  r = trace_reduce.reduce(trace_reduce.load(str(tmp_path)))
  assert 0.15 <= r["window_s"] < 5.0
  assert 0.0 < r["busy_s"] < r["window_s"]
  assert r["device_ops"] and len(r["device_ops"]) <= trace_reduce.TOP
  assert sum(s for _, s in r["device_ops"]) <= r["busy_s"] * 1.001
  assert trace_reduce.kernel_seconds(r, "dot") > 0
  assert trace_reduce.kernel_seconds(r, "no_such_kernel") == 0
  # the three sleeps are the longest idle gaps, each under its bench.host
  labels = [name for name, _ in r["idle_gaps"][:3]]
  assert labels == ["bench.host"] * 3
  assert all(0.04 < s < 1.0 for _, s in r["idle_gaps"][:3])


def test_op_name_is_the_hlo_instruction_name():
  # as a v5e trace names the paged exact kernel's events
  assert trace_reduce.op_name(
      "%paged_flash_decode.15 = f32[128,7,128]{2,1,0:T(8,128)S(1)} "
      "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"") == (
          "paged_flash_decode.15")
  assert trace_reduce.op_name("dot.3") == "dot.3"


def test_union_merges_overlaps():
  assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
      (0, 3), (5, 8)]


def test_roofline_share_refuses_a_chip_trace_without_its_kernel():
  ops = {"fusion.3": 0.5, "copy.1": 0.1}
  run = SimpleNamespace(
      trace=dict(ops=ops, device_ops=[list(kv) for kv in ops.items()]),
      peaks=dict(bf16_flops=197e12, hbm_bytes_per_s=819e9))
  with pytest.raises(ValueError, match="paged_flash_decode"):
    stats.roofline_share(run, paged_flash_decode)
  run.peaks = None                # off the chip the reader stays silent
  assert stats.roofline_share(run, paged_flash_decode) is None
