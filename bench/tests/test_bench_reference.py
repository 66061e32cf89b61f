"""The float32 reference against the engine's prefill-then-decode at a
reduced size, for the exact and the PQ policies: the engine's greedy
tokens are the reference's own.  The PQ case runs with a body: prompts
longer than sink + recent, decoded past more ring wraps, so the prefill's
k-means, the codes in the pool, the ring and the decode logits are all on
the path.  Exact runs read a gap of 0; PQ may read a sliver above it where
the program's bfloat16 codebook storage sends a token to a neighbouring
centroid: at K=16 a neighbour lies far, so such a token moves the logits
by tenths; at least four tokens in five are the reference's own."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, drive, reference, run, spec  # noqa: E402

HF = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
          num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
          vocab_size=256, rope_theta=1e6, rms_norm_eps=1e-5,
          torch_dtype="float32")
PAGED = dict(cache_layout="paged", scheduler="paged", kv_block_size=16,
             decode_kernel="auto")


PQ = dict(PAGED, cache_policy="pq", pq_m=4, pq_k=16, pq_sink=4, pq_recent=8)


@pytest.mark.parametrize("serving,seed,tol,share", [
    (dict(PAGED, cache_policy="exact"), 3, 0.0, 1.0),
    (PQ, 3, 0.5, 0.8),
    (PQ, 4, 0.5, 0.8),
    (PQ, 5, 0.5, 0.8),
])
def test_engine_tokens_are_reference_greedy(serving, seed, tol, share):
  from repro.launch.engine import ServeEngine
  dense = spec.family("dense")
  cfg = run.model_config(dict(arch="yi-34b", hf_config=HF, serving=serving),
                         dense)
  w = dense.make(HF, seed, cfg.dtype)
  eng = ServeEngine(cfg, params=w, context_len=128, prompt_capacity=96,
                    max_batch=2)
  rng = np.random.default_rng(0)
  prompts, new = (90, 37, 61), 30
  hs = [eng.submit(rng.integers(1, 256, size=p), new) for p in prompts]
  while eng.has_work:
    eng.step()
  gaps = check.gaps(w, HF, dense, [drive.Served(h) for h in hs], 128,
                    pq=reference.pq_spec(serving, 128))
  assert [len(g) for g in gaps] == [new] * len(prompts)
  g = np.concatenate(gaps)
  assert g.max() <= tol
  assert np.mean(g == 0.0) >= share


def test_reference_follows_weight_dims():
  d = spec.family("dense").dims(dict(HF, head_dim=None))
  assert (d["hd"], d["layers"], d["v"]) == (16, 2, 256)
