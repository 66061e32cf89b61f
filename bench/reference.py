"""Plain float32 reference of a decoder, computed in blocks.

What every family shares lives here: the embedding, RMSNorm, rotate-half
RoPE, grouped-query causal attention and an untied LM head, as the Mistral
and Yi model cards describe them.  The rest of a layer (for the dense
family the SwiGLU MLP) is the family's `block`, from
`families/<family>.py`; a family may bring its whole layer body as
`layer`.  Both see the family's settings, its `dims` of the configuration
(`settings_of`), and the shared attention applies each layer's window and
RoPE from them.  It imports nothing of the program: it reads the
benchmark's own weights (the family's `make`) and the tokens the program
served.  Matrix products run at `Precision.HIGHEST`.  One layer is upcast
to float32 at a time and the attention runs over blocks of query rows, so
the reference fits beside the weights on one chip.

For a PQ configuration (`pq_spec`) the reference follows AQPIM's cache
semantics as well, from the paper (sec. III) and the configuration alone:
per layer and kv head, the prompt's body tokens (positions [sink, prompt -
recent)) are clustered per subvector by importance-weighted k-means
(weights: the softmax mass the lead query head of the group puts on each
token over the prompt's last `recent` queries; strided initial centroids
over the body capacity; `iters` Lloyd steps; an empty cluster keeps its
centroid), and every decode row attends exactly to the sink tokens and
to its last `recent` tokens and, through the codebooks, to the body
between them: each body token's key and value replaced by the nearest
centroid of each subvector, read back from codebooks stored in bfloat16,
the storage format of the configuration's cache.  Prompt rows attend
exactly, as the prefill does.

`low=True` is the control: the same computation with both operands of
every matrix product rounded to float8 (e4m3, one scale per tensor), the
precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Q_BLOCK = 512
ROW_BUCKET = 128
PQ_ITERS = 4       # the paper's k-means iterations (sec. III-B)


def _fp8(x):
  s = jnp.max(jnp.abs(x)) / E4M3_MAX
  s = jnp.where(s > 0, s, 1.0)
  return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec, a, b, low):
  if low:
    a, b = _fp8(a), _fp8(b)
  return jnp.einsum(spec, a, b, precision=HIGHEST,
                    preferred_element_type=jnp.float32)


def rms(x, w, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def pq_spec(serving: dict, context_len: int):
  """(sink, recent, m, K, iters, body capacity) of a PQ configuration, or
  None for an exact one.  The body capacity is the context less sink and
  recent, rounded up to whole 512-token (64 below a 4k context) blocks."""
  if serving["cache_policy"] != "pq":
    return None
  sink, recent = serving["pq_sink"], serving["pq_recent"]
  blk = 512 if context_len >= 4096 else 64
  nb = -(-max(context_len - sink - recent, 1) // blk) * blk
  return (sink, recent, serving["pq_m"], serving["pq_k"], PQ_ITERS, nb)


def _importance(qh, kh, n_prompt, recent):
  """Softmax mass each token gets from the prompt's last `recent` queries
  (qh, kh: (S, hd) of one kv head, qh its group's lead query head)."""
  s = qh.shape[0]
  pos = jnp.arange(s)
  sc = jnp.einsum("id,jd->ij", qh, kh, precision=HIGHEST) * qh.shape[1] ** -0.5
  rows = (pos >= n_prompt - recent) & (pos < n_prompt)
  sc = jnp.where((pos[None, :] <= pos[:, None]) & rows[:, None], sc, -jnp.inf)
  p = jnp.where(rows[:, None], jax.nn.softmax(sc, axis=-1), 0.0)
  return jnp.where(pos < n_prompt, jnp.sum(p, axis=0), 0.0)


def _pq_recon(xh, w, n_prompt, pq, low):
  """xh (S, hd) of one kv head with every token at or past `sink` replaced
  by its nearest centroid per subvector, codebooks from the prompt's body."""
  sink, recent, m, k_cent, iters, nb = pq
  s, hd = xh.shape
  dsub = hd // m
  body = jnp.pad(xh, ((0, max(sink + nb - s, 0)), (0, 0)))[sink:sink + nb]
  wb = jnp.pad(w, (0, max(sink + nb - s, 0)))[sink:sink + nb]
  mask = jnp.arange(nb) < jnp.clip(n_prompt - sink - recent, 0, nb)
  wb = jnp.where(mask, wb, 0.0)
  wb = jnp.where(jnp.sum(wb) > 0, wb, 1.0)
  init = jnp.where(mask[:, None], body, body[0])
  sub = lambda x: x.reshape(x.shape[0], m, dsub).transpose(1, 0, 2)
  stride = max(nb // k_cent, 1)
  cb = sub(init)[:, (jnp.arange(k_cent) * stride) % nb]       # (m, K, dsub)

  def nearest(x, cb):                                         # (m, N, dsub)
    d2 = (jnp.sum(x * x, -1)[..., None]
          - 2 * mm("mnd,mkd->mnk", x, cb, low)
          + jnp.sum(cb * cb, -1)[:, None, :])
    return jnp.argmin(d2, axis=-1)

  xb = sub(body)
  for _ in range(iters):
    one = jax.nn.one_hot(nearest(xb, cb), k_cent) * wb[None, :, None]
    num = mm("mnk,mnd->mkd", one, xb, low)
    den = jnp.sum(one, axis=1)[..., None]
    cb = jnp.where(den > 1e-12, num / jnp.maximum(den, 1e-12), cb)
  # codebooks are stored in bfloat16: the prompt's body is coded with the
  # float32 centroids k-means ends with, the tokens that leave the recent
  # window during decode with the stored ones, and both read back stored
  stored = cb.astype(jnp.bfloat16).astype(jnp.float32)
  xs = sub(xh)
  late = (jnp.arange(s) >= n_prompt - recent)[None, :]
  codes = jnp.where(late, nearest(xs, stored), nearest(xs, cb))  # (m, S)
  rec = jnp.take_along_axis(stored, codes[..., None], axis=1)  # (m, S, dsub)
  return rec.transpose(1, 0, 2).reshape(s, hd)


class Settings(dict):
  """A family's `dims`: sizes and settings, hashable so that a jitted layer
  takes them as one static argument."""

  def __hash__(self):
    return hash(tuple(sorted(self.items())))


def settings_of(family, hf: dict) -> Settings:
  """`family.dims(hf)`, checked: a window and a RoPE for every layer.

  `windows[i]` is None for full causal attention, or W: query i sees keys
  j with i - W < j <= i (HF's `sliding_window`).  `rope[i]` is (inverse
  frequencies, amplitude): the frequencies as a tuple of head_dim / 2
  floats, or a theta for HF's default 1/theta^(j/half); the amplitude
  multiplies cos and sin, as YaRN's attention factor does."""
  s = Settings(family.dims(hf))
  n = s["layers"]
  if len(s["windows"]) != n or len(s["rope"]) != n:
    raise ValueError(f"{n} layers need {n} windows and {n} RoPE entries, "
                     f"not {len(s['windows'])} and {len(s['rope'])}")
  if any(w is not None and (not isinstance(w, int) or w < 1)
         for w in s["windows"]):
    raise ValueError(f"a window is None or a whole number of at least 1, "
                     f"not {s['windows']}")
  return s


def _inv_freq(base, half):
  if isinstance(base, tuple):
    return jnp.asarray(base, jnp.float32)
  return 1.0 / base ** (jnp.arange(half, dtype=jnp.float32) / half)


def _rope(x, rope, i):
  """x (S, H, hd): rotate-half RoPE at positions 0..S-1 with layer i's
  entry of `rope` (`settings_of`)."""
  s, _, hd = x.shape
  half = hd // 2
  amp = None
  if len(set(rope)) == 1:
    base, a = rope[0]
    inv = _inv_freq(base, half)
    if a != 1.0:
      amp = a
  else:
    inv = jnp.stack([_inv_freq(b, half) for b, _ in rope])[i]
    amp = jnp.asarray([a for _, a in rope], jnp.float32)[i]
  ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
  cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
  if amp is not None:
    cos, sin = cos * amp, sin * amp
  x1, x2 = x[..., :half], x[..., half:]
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _visible(kpos, qpos, windows, i):
  """Keys each query row sees in layer i: causal, and inside the layer's
  window where it has one."""
  seen = kpos[None, :] <= qpos[:, None]
  if all(w is None for w in windows):
    return seen
  w = jnp.asarray([kpos.shape[0] if w is None else w for w in windows],
                  jnp.int32)[i]
  return seen & (kpos[None, :] > qpos[:, None] - w)


def attention(x, lw, i, n_prompt, eps, low, pq, settings):
  """Layer i's attention with its norm and residual, traced inside a layer
  body: x (S, D), lw the layer's weights upcast to float32, `settings` the
  family's (`settings_of`)."""
  s = x.shape[0]
  windows, rope = settings["windows"], settings["rope"]
  if pq is not None and any(w is not None for w in windows):
    raise ValueError("the PQ reference has no sliding window; a PQ "
                     "configuration with windowed layers is not supported")
  at = lw["attn"]
  hkv, hd = at["wk"].shape[1], at["wk"].shape[2]
  g = at["wq"].shape[1] // hkv
  h = rms(x, lw["ln1"]["scale"], eps)
  q = _rope(mm("sd,dhk->shk", h, at["wq"], low), rope, i)
  k = _rope(mm("sd,dhk->shk", h, at["wk"], low), rope, i)
  v = mm("sd,dhk->shk", h, at["wv"], low)
  qg = q.reshape(s, hkv, g, hd)
  kpos = jnp.arange(s)
  if pq is not None:
    def head(args):
      qh, kh, vh = args
      w = _importance(qh, kh, n_prompt, pq[1])
      return (_pq_recon(kh, w, n_prompt, pq, low),
              _pq_recon(vh, w, n_prompt, pq, low))
    k_hat, v_hat = jax.lax.map(head, (jnp.moveaxis(qg[:, :, 0], 1, 0),
                                      jnp.moveaxis(k, 1, 0),
                                      jnp.moveaxis(v, 1, 0)))
    k_hat, v_hat = jnp.moveaxis(k_hat, 0, 1), jnp.moveaxis(v_hat, 0, 1)

  def q_block(start):
    qb = jax.lax.dynamic_slice_in_dim(qg, start, Q_BLOCK, 0)
    sc = mm("bhgk,thk->hgbt", qb, k, low) * hd ** -0.5
    qpos = start + jnp.arange(Q_BLOCK)
    if pq is not None:
      # decode rows read body tokens [sink, row - recent] through the codes
      coded = ((qpos[:, None] >= n_prompt) & (kpos[None, :] >= pq[0])
               & (kpos[None, :] <= qpos[:, None] - pq[1]))
      sc = jnp.where(coded, mm("bhgk,thk->hgbt", qb, k_hat, low)
                     * hd ** -0.5, sc)
    sc = jnp.where(_visible(kpos, qpos, windows, i), sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    if pq is None:
      return mm("hgbt,thk->bhgk", p, v, low).reshape(Q_BLOCK, hkv * g, hd)
    o = (mm("hgbt,thk->bhgk", jnp.where(coded, 0.0, p), v, low)
         + mm("hgbt,thk->bhgk", jnp.where(coded, p, 0.0), v_hat, low))
    return o.reshape(Q_BLOCK, hkv * g, hd)

  o = jax.lax.map(q_block, jnp.arange(0, s, Q_BLOCK)).reshape(s, hkv * g, hd)
  return x + mm("shk,hkd->sd", o, at["wo"], low)


@functools.partial(jax.jit,
                   static_argnames=("eps", "low", "pq", "settings", "block"))
def _layer(x, layers, i, n_prompt, eps, low, pq, settings, block):
  """Layer `i`: the shared attention, then the family's `block`."""
  lw = jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)
  return block(attention(x, lw, i, n_prompt, eps, low, pq, settings), lw,
               eps, low, settings)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _logits(x, norm, head, eps, low):
  h = rms(x, norm.astype(jnp.float32), eps)
  return mm("sd,dv->sv", h, head.astype(jnp.float32), low)


@jax.jit
def _gap(logits, picked):
  """How far each picked token's logit lies below the row's best."""
  got = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
  return jnp.max(logits, axis=-1) - got


def hidden(w: dict, hf: dict, family, tokens: np.ndarray, s_pad: int,
           low: bool, pq=None, n_prompt: int = 0) -> jax.Array:
  """Final hidden states (s_pad, D) of `tokens`, right-padded to s_pad,
  through the layers of `family` (a `families/<family>.py` module); with
  `pq`, rows from `n_prompt` on read the body through codebooks."""
  s_pad = -(-s_pad // Q_BLOCK) * Q_BLOCK
  ids = np.zeros((s_pad,), np.int32)
  ids[:len(tokens)] = tokens
  x = jnp.take(w["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
  eps, settings = float(hf["rms_norm_eps"]), settings_of(family, hf)
  layer = getattr(family, "layer", None) or functools.partial(
      _layer, block=family.block)
  for i in range(settings["layers"]):
    x = layer(x, w["layers"], jnp.int32(i), jnp.int32(n_prompt), eps, low,
              pq, settings)
  return x


def served_gaps(w: dict, hf: dict, family, prompt: np.ndarray,
                served: np.ndarray, s_pad: int, control: bool = False,
                pq=None) -> np.ndarray:
  """For each served token, the gap by which the reference's logit for it
  lies below the reference's best at that position.

  With `control`, the float8 reference takes the program's place: each
  position's token is the one float8 logits put first, read against the
  float32 reference at the same position of the same prompt and tokens.
  """
  seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
  n = len(served)
  # rows padded to a multiple of ROW_BUCKET so few logits shapes compile
  rows = np.full((-(-n // ROW_BUCKET) * ROW_BUCKET,), len(seq) - 1)
  rows[:n] = np.arange(len(prompt) - 1, len(seq))
  eps = float(hf["rms_norm_eps"])
  x = hidden(w, hf, family, seq, s_pad, False, pq, len(prompt))
  ref = _logits(x[rows], w["final_norm"]["scale"], w["lm_head"], eps, False)
  if control:
    xl = hidden(w, hf, family, seq, s_pad, True, pq, len(prompt))
    low = _logits(xl[rows], w["final_norm"]["scale"], w["lm_head"], eps,
                  True)
    picked = jnp.argmax(low, axis=-1).astype(jnp.int32)
  else:
    picked = np.zeros(rows.shape, np.int32)
    picked[:n] = served
    picked = jnp.asarray(picked)
  return np.asarray(_gap(ref, picked))[:n]
