"""Model weights drawn from the seed on the device, in one jitted call.

The benchmark makes the weights, not the program, so that the reference
in `reference.py` reads weights the program did not make.  The tree is
the one the program's dense decoder takes (`embed`, `final_norm`,
`lm_head`, and `layers` stacked on a leading layer axis); `run.py` checks
it against the program's own tree before serving.  Norm scales are drawn
around 1, not set to 1, so that a norm which ignores its scale shows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dims(hf: dict) -> dict:
  """Sizes the weights and the reference need, from an HF-style config."""
  d = hf["hidden_size"]
  h = hf["num_attention_heads"]
  return dict(d=d, h=h, hkv=hf["num_key_value_heads"],
              hd=hf.get("head_dim") or d // h, f=hf["intermediate_size"],
              v=hf["vocab_size"], layers=hf["num_hidden_layers"])


def key(seed: int) -> jax.Array:
  """A PRNG key from any non-negative seed, 64-bit ones included."""
  words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
  return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _make(k, shape, dtype):
  d, h, hkv, hd, f, v, n = shape
  ks = iter(jax.random.split(k, 12))

  def dense(fan_in, shp):
    return (jax.random.normal(next(ks), shp, jnp.float32)
            * fan_in ** -0.5).astype(dtype)

  def scale(shp):
    return (1.0 + 0.1 * jax.random.normal(next(ks), shp, jnp.float32)
            ).astype(dtype)

  return {
      "embed": (0.02 * jax.random.normal(next(ks), (v, d), jnp.float32)
                ).astype(dtype),
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
  return _make(key(seed), shape, jnp.dtype(dtype))
