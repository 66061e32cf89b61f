"""The dense decoder family: grouped-query attention with RoPE, then a
SwiGLU MLP, in every layer (Mistral, Yi).

A family file holds what the harness needs to know of one model family,
found by the `"family"` a configuration file names (`spec.family`):

  HF_KEYS             its HF config keys -> program `ModelConfig` fields,
                      over the common ones in `run.py`
  dims(hf)            the one place the family reads its configuration:
                      the sizes `make`, the reference and the costs take,
                      and its settings, as hashable values (ints, floats,
                      None, tuples) that reach `block` and `layer`; every
                      family gives `windows` and `rope`, one entry a layer
                      (`reference.settings_of` says what they mean)
  make(hf, seed, dtype)  the program's parameter tree, drawn from the seed
                      on the device in one jitted call
  block(x, lw, eps, low, settings)  the reference's layer after the shared
                      attention (`reference.attention`, which applies the
                      layer's window and RoPE), with its norm and residual;
                      lw is the layer's weights upcast to float32,
                      `settings` the family's `dims`
  layer_weight_flops(shape)  the weights one live row multiplies in one
                      layer (`costs/decode_step.py` doubles it and adds the
                      LM head and the attention, counted at each layer's
                      window)

and, where the shared attention does not fit the family, `layer(x,
layers, i, n_prompt, eps, low, pq, settings)`, a jitted layer body that
the reference runs in place of attention and `block`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as ref
from bench import weights as wlib

HF_KEYS = dict(intermediate_size="d_ff")


def dims(hf: dict) -> dict:
  """Sizes the weights and the reference need, from an HF-style config;
  every layer attends over the whole length with HF's default RoPE at
  `rope_theta`, amplitude 1."""
  d = hf["hidden_size"]
  h = hf["num_attention_heads"]
  n = hf["num_hidden_layers"]
  return dict(d=d, h=h, hkv=hf["num_key_value_heads"],
              hd=hf.get("head_dim") or d // h, f=hf["intermediate_size"],
              v=hf["vocab_size"], layers=n, windows=(None,) * n,
              rope=((float(hf["rope_theta"]), 1.0),) * n)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _make(k, shape, dtype):
  d, h, hkv, hd, f, v, n = shape
  ks = iter(jax.random.split(k, 12))

  def dense(fan_in, shp):
    return wlib.dense(next(ks), fan_in, shp, dtype)

  def scale(shp):
    return wlib.scale(next(ks), shp, dtype)

  return {
      "embed": wlib.embed(next(ks), (v, d), dtype),
      "final_norm": {"scale": scale((d,))},
      "lm_head": dense(d, (d, v)),
      "layers": {
          "ln1": {"scale": scale((n, d))},
          "attn": {"wq": dense(d, (n, d, h, hd)),
                   "wk": dense(d, (n, d, hkv, hd)),
                   "wv": dense(d, (n, d, hkv, hd)),
                   "wo": dense(h * hd, (n, h, hd, d))},
          "ln2": {"scale": scale((n, d))},
          "mlp": {"w_gate": dense(d, (n, d, f)),
                  "w_up": dense(d, (n, d, f)),
                  "w_down": dense(f, (n, f, d))},
      },
  }


def make(hf: dict, seed: int, dtype=jnp.bfloat16) -> dict:
  s = dims(hf)
  shape = (s["d"], s["h"], s["hkv"], s["hd"], s["f"], s["v"], s["layers"])
  return _make(wlib.key(seed), shape, jnp.dtype(dtype))


def block(x, lw, eps, low, settings):
  """The SwiGLU MLP after the second norm, with its residual."""
  del settings
  h = ref.rms(x, lw["ln2"]["scale"], eps)
  mlp = lw["mlp"]
  a = jax.nn.silu(ref.mm("sd,df->sf", h, mlp["w_gate"], low))
  a = a * ref.mm("sd,df->sf", h, mlp["w_up"], low)
  return x + ref.mm("sf,fd->sd", a, mlp["w_down"], low)


def layer_weight_flops(shape: dict) -> int:
  """Weights one live row multiplies in one layer: the q, k, v and output
  projections and the three SwiGLU matrices (the caller counts 2 FLOPs a
  weight)."""
  d, h, hkv, hd, f = (shape[k] for k in ("d", "h", "hkv", "hd", "f"))
  return d * hd * (h + 2 * hkv) + h * hd * d + 3 * d * f
