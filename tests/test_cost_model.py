"""Validate the analytic roofline cost model against compiled HLO.

Compiled cost_analysis counts while-loop bodies once, so validation uses
UNROLLED layers and single-block attention on small configs (loop-free HLO),
on a single device (cost_analysis reports per-partition numbers).
Families with sequential-scan recurrences (rwkv/ssm) cannot be made loop-free
and are excluded here; their per-token recurrence flops are hand-derived in
cost_model and covered indirectly by the dense/hybrid linear parts.
"""
import dataclasses

import jax
import pytest

from benchmarks import cost_model
from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.launch import steps as steps_lib


def _compiled_flops(cfg, shape):
  mesh = jax.make_mesh((1, 1), ("data", "model"))
  with mesh:
    progs = steps_lib.build_programs(cfg, shape, mesh, donate=False)
    compiled = progs.fn.lower(*progs.abstract_inputs).compile()
    ca = compiled.cost_analysis()
    return float(ca.get("flops", 0.0))


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "llama-3.2-vision-11b"])
def test_train_flops_within_band(arch):
  cfg = dataclasses.replace(
      get_arch(arch, reduced=True),
      n_layers=2, unroll_layers=True, remat=False, attn_block=128,
      microbatches=1,   # microbatch scan bodies are cost-counted once
      cross_attn_period=2 if arch == "llama-3.2-vision-11b" else 0)
  if arch == "llama-3.2-vision-11b":
    cfg = dataclasses.replace(cfg, cross_attn_period=2)
  shape = ShapeConfig("t", 128, 8, "train")
  compiled = _compiled_flops(cfg, shape)
  analytic = cost_model.train_step_flops(cfg, 8, 128)
  ratio = analytic / compiled
  assert 0.4 < ratio < 1.5, (arch, compiled, analytic, ratio)


@pytest.mark.parametrize("arch,pq", [("tinyllama-1.1b", True),
                                     ("tinyllama-1.1b", False)])
def test_decode_flops_within_band(arch, pq):
  cfg = dataclasses.replace(
      get_arch(arch, reduced=True),
      n_layers=2, unroll_layers=True, pq_enabled=pq)
  shape = ShapeConfig("d", 256, 4, "decode")
  compiled = _compiled_flops(cfg, shape)
  analytic = cost_model.decode_step_flops(cfg, 4, 256)
  ratio = analytic / compiled
  # tiny reduced dims: fixed overheads dominate -> wide band
  assert 0.3 < ratio < 2.0, (pq, compiled, analytic, ratio)


def test_pq_reduces_decode_memory_term():
  """The paper's headline on our cost model: PQ cuts decode HBM bytes."""
  cfg = get_arch("llama3-405b")
  exact = cost_model.kv_cache_bytes(
      dataclasses.replace(cfg, pq_enabled=False), 128, 32768)
  pq = cost_model.kv_cache_bytes(cfg, 128, 32768)
  assert exact / pq > 3.0, exact / pq
  # uint8 variant (K=256) doubles the reduction
  pq8 = cost_model.kv_cache_bytes(
      dataclasses.replace(cfg, pq_k=256), 128, 32768)
  assert exact / pq8 > 6.0, exact / pq8


def test_int8_weights_halve_param_bytes():
  cfg = get_arch("llama3-405b")
  b_bf16 = cost_model.param_bytes(cfg)
  b_int8 = cost_model.param_bytes(
      dataclasses.replace(cfg, weight_quant="int8"))
  assert 1.8 < b_bf16 / b_int8 < 2.1


def test_parallel_block_halves_tp_collectives():
  # dense arch: pblock halves the TP ARs.  (EP-MoE layers have no MLP-region
  # AR to begin with, so pblock is a no-op there — also asserted.)
  cfg = get_arch("yi-34b")
  base = cost_model.train_collective_bytes(cfg, 256, 4096, 16, 16)
  opt = cost_model.train_collective_bytes(
      dataclasses.replace(cfg, parallel_block=True), 256, 4096, 16, 16)
  assert opt < base
  moe = get_arch("phi3.5-moe-42b-a6.6b")
  m_base = cost_model.train_collective_bytes(moe, 256, 4096, 16, 16)
  m_opt = cost_model.train_collective_bytes(
      dataclasses.replace(moe, parallel_block=True), 256, 4096, 16, 16)
  assert m_opt == m_base


def test_moe_a2a_quant_reduces_collectives():
  cfg = get_arch("phi3.5-moe-42b-a6.6b")
  base = cost_model.train_collective_bytes(cfg, 256, 4096, 16, 16)
  opt = cost_model.train_collective_bytes(
      dataclasses.replace(cfg, moe_a2a_quant=True), 256, 4096, 16, 16)
  assert opt < base


def test_context_parallel_cuts_prefill_collectives():
  cfg = get_arch("tinyllama-1.1b")
  base = cost_model.prefill_collective_bytes(cfg, 32, 32768, 16, 16)
  opt = cost_model.prefill_collective_bytes(
      dataclasses.replace(cfg, context_parallel=True), 32, 32768, 16, 16)
  assert opt < base / 4
