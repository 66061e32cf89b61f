"""Model families found by name: a family added as files alone is served and
judged by its own reference; the dense family's weights and step FLOPs
are pinned to the values the harness read before families were files of
their own (the same seed and shapes, computed once from that tree)."""
import hashlib
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec  # noqa: E402
from bench.costs import decode_step, paged_flash_decode  # noqa: E402

HF = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
          num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
          vocab_size=256, rope_theta=1e6, rms_norm_eps=1e-5,
          torch_dtype="float32")
EXACT = dict(cache_policy="exact", cache_layout="paged", scheduler="paged",
             kv_block_size=16, decode_kernel="auto")
SHORT = dict(traffic="closed_sessions",
             engine=dict(context_len=64, prompt_capacity=48, max_batch=4),
             params=dict(sessions=4, prompt_len=[40, 48], refills=8),
             check=dict(sample=3, limits=dict(max_logit_gap=1e-3)))
E2E = [dict(name=n, unit="u") for n in ("decode_tok_s", "setup_s")]

# appended to a copy of dense.py: a later definition replaces dense's
NO_MLP = '''
def block(x, lw, eps, low):
  return x
'''
OWN_LAYER = '''
@functools.partial(jax.jit, static_argnames=("eps", "theta", "low", "pq"))
def layer(x, layers, i, n_prompt, eps, theta, low, pq):
  lw = jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)
  return block(ref.attention(x, lw, n_prompt, eps, theta, low, pq), lw,
               eps, low)
'''
OWN_LAYER_NO_ATTENTION = '''
@functools.partial(jax.jit, static_argnames=("eps", "theta", "low", "pq"))
def layer(x, layers, i, n_prompt, eps, theta, low, pq):
  lw = jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)
  return block(x, lw, eps, low)
'''


@pytest.mark.parametrize("extra,correct", [
    ("", True),                          # dense, copied under another name
    (NO_MLP, False),                     # its reference leaves out the MLP
    (OWN_LAYER, True),                   # brings its own layer body
    (OWN_LAYER_NO_ATTENTION, False),     # ... which leaves out attention
], ids=["copy", "no-mlp", "own-layer", "own-layer-no-attention"])
def test_planted_family_is_served_and_judged(tmp_path, monkeypatch, extra,
                                             correct):
  src = (spec.FAMILIES / "dense.py").read_text()
  (tmp_path / "planted.py").write_text(src + extra)
  conf = dict(name="planted", arch="yi-34b", family="planted", hf_config=HF,
              serving=EXACT)
  (tmp_path / "planted.json").write_text(json.dumps(conf))
  monkeypatch.setattr(spec, "FAMILIES", tmp_path)
  cell = dict(config=json.loads((tmp_path / "planted.json").read_text()),
              workload=SHORT)
  out = run.run_cell("cell", cell, E2E, 2**31 + 5, 1.0, False,
                     time.perf_counter())
  assert out["checks"]["tokens_compared"]["value"] > 0
  assert out["failed"] == 0
  assert out["correct"] is correct, out["checks"]


def test_a_configuration_names_its_family():
  cell = dict(config=dict(arch="yi-34b", hf_config=HF, serving=EXACT),
              workload=SHORT)
  with pytest.raises(KeyError, match="family"):
    run.serve(cell, 1, 1.0, False, time.perf_counter())


def _digest(tree) -> str:
  h = hashlib.sha256()
  leaves = jax.tree_util.tree_leaves_with_path(tree)
  for _, leaf in sorted(leaves, key=lambda t: jax.tree_util.keystr(t[0])):
    h.update(np.asarray(leaf).tobytes())
  return h.hexdigest()


def test_dense_weights_are_pinned():
  w = spec.family("dense").make(HF, seed=7, dtype=jnp.float32)
  assert _digest(w) == (
      "ac24e4dcd85c42a4fba8a5103a2649af1250885d2067e5531211337a20534109")


def test_dense_step_flops_are_pinned():
  conf = spec.load_json("bench/configs/yi-34b-exact.json")
  shape = run.shape(conf, spec.family(conf["family"]), 4096)
  lengths = [1536, 3072, 4095]
  assert decode_step.flops(lengths, shape) == 17143104384.0
  assert paged_flash_decode.cost(lengths, shape) == (250593504.0, 35788800.0)
