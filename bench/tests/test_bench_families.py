"""Model families found by name: a family added as files alone is served and
judged by its own reference; what its `dims` reads from the configuration
(experts per token, a window and a RoPE per layer) reaches its block, the
shared attention and the cost counts; the dense family's weights, step
FLOPs and reference layer program are pinned to what the harness had
before (the same seed and shapes, computed once from that tree)."""
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

from bench import reference, run, spec  # noqa: E402
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
def block(x, lw, eps, low, settings):
  return x
'''
OWN_LAYER = '''
@functools.partial(jax.jit, static_argnames=("eps", "low", "pq", "settings"))
def layer(x, layers, i, n_prompt, eps, low, pq, settings):
  lw = jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)
  return block(ref.attention(x, lw, i, n_prompt, eps, low, pq, settings),
               lw, eps, low, settings)
'''
OWN_LAYER_NO_ATTENTION = '''
@functools.partial(jax.jit, static_argnames=("eps", "low", "pq", "settings"))
def layer(x, layers, i, n_prompt, eps, low, pq, settings):
  lw = jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)
  return block(x, lw, eps, low, settings)
'''
# an expert and windowed family, as its configuration states it: the dense
# MLP's columns as experts, a window and a RoPE for each kind of layer
HYBRID = '''
import numpy as np

_dense_dims = dims


def dims(hf):
  out = _dense_dims(hf)
  half = out["hd"] // 2
  rope = {}
  for kind, p in hf["rope_parameters"].items():
    inv = 1.0 / p["rope_theta"] ** (np.arange(half) / half)
    base = (p["rope_theta"] if p.get("factor") is None
            else tuple(float(f) for f in inv / p["factor"]))
    rope[kind] = (base, p.get("attention_factor", 1.0))
  kinds = hf["layer_types"][:out["layers"]]
  out.update(experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
             windows=tuple(hf["sliding_window"] if k == "sliding_attention"
                           else None for k in kinds),
             rope=tuple(rope[k] for k in kinds))
  return out


def block(x, lw, eps, low, settings):
  h = ref.rms(x, lw["ln2"]["scale"], eps)
  mlp = lw["mlp"]
  a = jax.nn.silu(ref.mm("sd,df->sf", h, mlp["w_gate"], low))
  a = a * ref.mm("sd,df->sf", h, mlp["w_up"], low)
  a = a.reshape(a.shape[0], settings["experts"], -1)
  score = jnp.sum(a * a, -1)
  keep = score >= jax.lax.top_k(score, settings["top_k"])[0][:, -1:]
  a = jnp.where(keep[..., None], a, 0.0).reshape(a.shape[0], -1)
  return x + ref.mm("sf,fd->sd", a, mlp["w_down"], low)
'''
HYBRID_HF = dict(
    HF, layer_types=["sliding_attention", "full_attention"], sliding_window=8,
    num_experts=4, num_experts_per_tok=2,
    rope_parameters=dict(
        sliding_attention=dict(rope_theta=1e6),
        full_attention=dict(rope_theta=1e4, factor=4.0,
                            attention_factor=1.3)))


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


@pytest.fixture
def hybrid(tmp_path, monkeypatch):
  src = (spec.FAMILIES / "dense.py").read_text()
  (tmp_path / "hybrid.py").write_text(src + HYBRID)
  monkeypatch.setattr(spec, "FAMILIES", tmp_path)
  return spec.family("hybrid")


def test_settings_reach_the_block(hybrid):
  # reference only: experts per token come from the configuration alone
  w = hybrid.make(HYBRID_HF, 3, jnp.float32)
  tokens = np.random.default_rng(0).integers(1, 256, size=40)
  out = [np.asarray(reference.hidden(
      w, dict(HYBRID_HF, num_experts_per_tok=k), hybrid, tokens, 40,
      False))[:40] for k in (1, 2, 4)]
  for a, b in [(0, 1), (1, 2), (0, 2)]:
    assert np.abs(out[a] - out[b]).max() > 1e-3


def _brute_attention(x, lw, eps, window, inv, amp):
  """A layer's attention with its norm and residual by loops in float64:
  query i sees keys j with i - window < j <= i, RoPE at `inv` times
  `amp`."""
  x = np.asarray(x, np.float64)
  at = {k: np.asarray(v, np.float64) for k, v in lw["attn"].items()}
  h = (x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps)
       * np.asarray(lw["ln1"]["scale"], np.float64))
  q, k, v = (np.einsum("sd,dhk->shk", h, at[n]) for n in ("wq", "wk", "wv"))
  s, hq, hd = q.shape
  g, half = hq // k.shape[1], hd // 2

  def rot(t, pos):
    c, sn = amp * np.cos(pos * inv), amp * np.sin(pos * inv)
    a, b = t[:half], t[half:]
    return np.concatenate([a * c - b * sn, b * c + a * sn])

  out = np.zeros((s, hq, hd))
  for i in range(s):
    lo = 0 if window is None else max(0, i - window + 1)
    for hh in range(hq):
      qi = rot(q[i, hh], i)
      sc = np.array([rot(k[j, hh // g], j) @ qi
                     for j in range(lo, i + 1)]) / np.sqrt(hd)
      p = np.exp(sc - sc.max())
      out[i, hh] = (p / p.sum()) @ v[lo:i + 1, hh // g]
  return x + np.einsum("shk,hkd->sd", out, at["wo"])


@pytest.mark.parametrize("name,hf,layer", [
    ("dense", HF, 0),                    # whole length, default RoPE
    ("hybrid", HYBRID_HF, 0),            # window 8, default RoPE at 1e6
    ("hybrid", HYBRID_HF, 1),            # whole length, own RoPE, amp 1.3
], ids=["dense", "window", "rope"])
def test_shared_attention_matches_brute_force(request, name, hf, layer):
  fam = request.getfixturevalue(name) if name == "hybrid" else spec.family(
      name)
  settings = reference.settings_of(fam, hf)
  w = fam.make(hf, 5, jnp.float32)
  lw = jax.tree.map(lambda a: np.asarray(a[layer], np.float32), w["layers"])
  s = 40
  x = np.zeros((reference.Q_BLOCK, hf["hidden_size"]), np.float32)
  x[:s] = np.random.default_rng(1).normal(size=(s, hf["hidden_size"]))
  got = np.asarray(reference.attention(
      jnp.asarray(x), lw, jnp.int32(layer), jnp.int32(s),
      hf["rms_norm_eps"], False, None, settings))[:s]
  base, amp = settings["rope"][layer]
  half = settings["hd"] // 2
  inv = (np.asarray(base) if isinstance(base, tuple)
         else 1.0 / base ** (np.arange(half) / half))
  want = _brute_attention(x[:s], lw, hf["rms_norm_eps"],
                          settings["windows"][layer], inv, amp)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
  plain = _brute_attention(x[:s], lw, hf["rms_norm_eps"], None,
                           1.0 / hf["rope_theta"] ** (np.arange(half) / half),
                           1.0)
  assert (np.abs(want - plain).max() > 1e-3) == (name == "hybrid")


# sha256 of the lowered layer program as the parent traced it; the values
# it computes vary on the CPU with the thread count, the program does not
@pytest.mark.parametrize("policy,low,digest", [
    ("exact", False,
     "2caf781541611096ff28fff21fe17cb893d781a99c85b0721639b0f1c23ab63c"),
    ("exact", True,
     "1b01531cd620d34a2c9d835466126b090c006157ece255500de093b7c6d531c3"),
    ("pq", False,
     "5dba62019ea4b45e492a42e5379660c15cabf0cf6f7c16efc392dcdbf15b2fa4"),
    ("pq", True,
     "163e73f386a78f9c519f13d28f1a122204ca193ee55685f2ec274479d92164ba"),
])
def test_no_window_layer_is_the_parents(policy, low, digest):
  dense = spec.family("dense")
  w = jax.eval_shape(lambda: dense.make(HF, 7, jnp.float32))
  x = jax.ShapeDtypeStruct((reference.Q_BLOCK, 64), jnp.float32)
  i = jax.ShapeDtypeStruct((), jnp.int32)
  pq = reference.pq_spec(dict(cache_policy=policy, pq_m=4, pq_k=16,
                              pq_sink=4, pq_recent=8), 128)
  text = reference._layer.lower(x, w["layers"], i, i, 1e-5, low, pq,
                                reference.settings_of(dense, HF),
                                dense.block).as_text()
  assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_planted_family_is_counted_at_its_windows(hybrid):
  conf = dict(family="hybrid", hf_config=HYBRID_HF, serving=EXACT)
  shape = run.shape(conf, hybrid, 64)
  assert shape["windows"] == (8, None) and shape["top_k"] == 2
  lengths = [5, 20, 100]
  weights = 3 * 2.0 * (2 * shape["layer_weight_flops"] + 64 * 256)
  assert decode_step.flops(lengths, shape) == (
      weights + paged_flash_decode.cost(lengths, shape, 8)[0]
      + paged_flash_decode.cost(lengths, shape)[0])


def test_pq_reference_refuses_a_window(hybrid):
  w = hybrid.make(HYBRID_HF, 3, jnp.float32)
  pq = reference.pq_spec(dict(cache_policy="pq", pq_m=4, pq_k=16, pq_sink=4,
                              pq_recent=8), 64)
  with pytest.raises(ValueError, match="window"):
    reference.hidden(w, HYBRID_HF, hybrid, np.arange(1, 41), 64, False, pq,
                     30)
