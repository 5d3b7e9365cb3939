"""Plain reference of a dense decoder with grouped-query attention.

The forward pass written out in ``jax.numpy`` at float32, every matrix
product to float32's precision: token embedding, per layer an RMS norm,
rotary query/key projections, causal softmax attention over all earlier
positions, the output projection and a SwiGLU feed-forward (in sequence,
or beside attention on one norm for a parallel block), a final RMS norm
and the unembedding.  No kernel, no cache layout, no batching: it imports
nothing of the system under test.

It draws the weights from the seed as the benchmark does
(``yardstick/weights.py``), a layer at a time: ``embed`` (V, d); per
layer ``ln1``/``ln2`` norm gains stored as offsets from 1,
``attn.wq`` (d, H, hd), ``wk``/``wv`` (d, Hkv, hd), ``wo`` (H, hd, d),
``mlp.w1``/``w3`` (d, f), ``w2`` (f, d); ``final_norm``; ``unembed``
(d, V) unless the embeddings are tied.

``fp8=True`` is the correctness check's control: the same computation
with the operands of every matrix product rounded to float8 e4m3, each
scaled by its largest magnitude (per token for activations, per matrix
for weights), as an fp8 deployment of a bfloat16 model would run it.

Long prompts are processed a layer at a time over all positions, with
attention in query blocks that each visit only the key blocks at or before
them (an online softmax), so that a 32768-token prompt fits beside the
weights.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                     # largest finite float8 e4m3 value
BLOCK = 512                        # query / key block of the attention
ROWS = 2048                        # token block of projections and FFN
EXTEND = 32                        # continuations are padded to multiples


def _q8(x: jax.Array, axes: Tuple[int, ...]) -> Tuple[jax.Array, jax.Array]:
  """float8 e4m3 values (held exactly in bf16) and their scale, one scale
  per slice over ``axes``."""
  x = x.astype(jnp.float32)
  amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
  s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
  return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), s


def _split3(x: jax.Array):
  """Three bf16 parts whose sum is the f32 ``x`` to f32's precision."""
  hi = x.astype(jnp.bfloat16)
  r = x - hi.astype(jnp.float32)
  mid = r.astype(jnp.bfloat16)
  return hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _wmm(eq: str, x, w, n_contract: int, fp8: bool):
  """Activation (tokens first, contracted axes last) times a bf16 weight.

  f32: the activation is split into three bf16 parts; each product of two
  bf16 numbers is exact and sums in f32, so this is the f32 product at
  ``HIGHEST`` without a float32 copy of the weight.  fp8: one scale per
  token and one per weight matrix (the weights are drawn within +-2 sd,
  so every channel shares the same largest magnitude)."""
  x = x.astype(jnp.float32)
  if not fp8:
    return sum(jnp.einsum(eq, p, w, preferred_element_type=jnp.float32)
               for p in _split3(x))
  x8, sx = _q8(x, tuple(range(x.ndim - n_contract, x.ndim)))
  w8, sw = _q8(w, tuple(range(w.ndim)))
  y = jnp.einsum(eq, x8, w8, preferred_element_type=jnp.float32)
  return y * sx.reshape(sx.shape[:1] + (1,) * (y.ndim - 1)) * sw.reshape(())


def _amm(eq: str, a, b, fp8: bool, a_axes, b_axes):
  """Activation times activation (attention) at f32 ``HIGHEST``; fp8:
  both rounded to float8 e4m3 first, scaled along the contracted axes."""
  a = a.astype(jnp.float32)
  b = b.astype(jnp.float32)
  if fp8:
    (a8, sa), (b8, sb) = _q8(a, a_axes), _q8(b, b_axes)
    a, b = a8.astype(jnp.float32) * sa, b8.astype(jnp.float32) * sb
  return jnp.einsum(eq, a, b, precision=HI,
                    preferred_element_type=jnp.float32)


def _norm(x, w, eps):
  x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
  return x * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
  """x (S, H, D), pos (S,): rotate the two halves of each head."""
  half = x.shape[-1] // 2
  freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  ang = pos[:, None].astype(jnp.float32) * freq
  cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
  x1, x2 = x[..., :half], x[..., half:]
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, q_pos, k_pos, fp8: bool):
  """Causal attention of q (Sq, Hkv, G, D) over k/v (Sk, Hkv, D), whose
  positions run 0, 1, ... Sk-1.  Each query block visits only the key
  blocks at or before its last position."""
  Sq, Hkv, G, D = q.shape
  Sk = k.shape[0]
  bq, bk = min(BLOCK, Sq), min(BLOCK, Sk)
  pq, pk = (-Sq) % bq, (-Sk) % bk
  # Padded queries are sliced off; padded keys sit past every position.
  q = jnp.pad(q, ((0, pq), (0, 0), (0, 0), (0, 0)))
  q_pos = jnp.pad(q_pos, (0, pq), mode="edge")
  k = jnp.pad(k, ((0, pk), (0, 0), (0, 0)))
  v = jnp.pad(v, ((0, pk), (0, 0), (0, 0)))
  k_pos = jnp.pad(k_pos, (0, pk), constant_values=2 ** 30)
  n_k = (Sk + pk) // bk
  scale = D ** -0.5

  def one(i):
    qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
    pi = jax.lax.dynamic_slice_in_dim(q_pos, i * bq, bq)
    n_kb = jnp.minimum(jnp.max(pi) // bk + 1, n_k)

    def body(j, carry):
      acc, m, l = carry
      kj = jax.lax.dynamic_slice_in_dim(k, j * bk, bk)
      vj = jax.lax.dynamic_slice_in_dim(v, j * bk, bk)
      pj = jax.lax.dynamic_slice_in_dim(k_pos, j * bk, bk)
      s = _amm("qhgd,khd->hgqk", qi, kj, fp8, (-1,), (-1,)) * scale
      s = jnp.where(pi[:, None] >= pj[None, :], s, -jnp.inf)
      m_new = jnp.maximum(m, jnp.max(s, -1))
      p = jnp.exp(s - m_new[..., None])
      c = jnp.exp(m - m_new)
      acc = acc * c[..., None] + _amm("hgqk,khd->hgqd", p, vj, fp8,
                                      (-1,), (0,))
      return acc, m_new, l * c + jnp.sum(p, -1)

    init = (jnp.zeros((Hkv, G, bq, D), jnp.float32),
            jnp.full((Hkv, G, bq), -jnp.inf, jnp.float32),
            jnp.zeros((Hkv, G, bq), jnp.float32))
    acc, _, l = jax.lax.fori_loop(0, n_kb, body, init)
    return jnp.moveaxis(acc / l[..., None], 2, 0)          # (bq, Hkv, G, D)

  out = jax.lax.map(one, jnp.arange((Sq + pq) // bq))
  return out.reshape(Sq + pq, Hkv, G, D)[:Sq]


def _by_rows(f, *xs):
  """f over blocks of ROWS tokens (all arguments are split by tokens)."""
  S = xs[0].shape[0]
  if S <= ROWS or S % ROWS:
    return f(*xs)
  n = S // ROWS
  out = jax.lax.map(lambda t: f(*t),
                    tuple(x.reshape((n, ROWS) + x.shape[1:]) for x in xs))
  return jax.tree.map(lambda y: y.reshape((S,) + y.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=("arch", "fp8"))
def _layer(x, past_k, past_v, lp, pos, *, arch, fp8: bool):
  """One layer (weights ``lp``) over x (S, d) at positions ``pos``,
  attending to the past keys/values (P, Hkv, D) and its own.  Returns
  (x, k, v)."""
  a = dict(arch)
  at, eps = lp["attn"], a["norm_eps"]
  H, Hkv, D = a["n_heads"], a["n_kv_heads"], a["head_dim"]

  def qkv(xr, pr):
    h = _norm(xr, lp["ln1"], eps)
    return (_rope(_wmm("sd,dhk->shk", h, at["wq"], 1, fp8), pr,
                  a["rope_theta"]),
            _rope(_wmm("sd,dhk->shk", h, at["wk"], 1, fp8), pr,
                  a["rope_theta"]),
            _wmm("sd,dhk->shk", h, at["wv"], 1, fp8))

  def ffn(h):
    m = lp["mlp"]
    g = _wmm("sd,df->sf", h, m["w1"], 1, fp8)
    u = _wmm("sd,df->sf", h, m["w3"], 1, fp8)
    return _wmm("sf,fd->sd", jax.nn.silu(g) * u, m["w2"], 1, fp8)

  def tail(xr, orows):
    mix = _wmm("shk,hkd->sd", orows, at["wo"], 2, fp8)
    if a["parallel_block"]:
      return xr + mix + ffn(_norm(xr, lp["ln1"], eps))
    xr = xr + mix
    return xr + ffn(_norm(xr, lp["ln2"], eps))

  q, k, v = _by_rows(qkv, x, pos)
  P = past_k.shape[0]
  k_all = jnp.concatenate([past_k, k]) if P else k
  v_all = jnp.concatenate([past_v, v]) if P else v
  k_pos = jnp.concatenate([jnp.arange(P, dtype=pos.dtype), pos]) if P \
      else pos
  o = _attend(q.reshape(-1, Hkv, H // Hkv, D), k_all, v_all, pos, k_pos,
              fp8).reshape(-1, H, D)
  return _by_rows(tail, x, o), k, v


@functools.partial(jax.jit, static_argnames=("arch", "fp8"))
def _logits(x, final_norm, w_out, *, arch, fp8: bool):
  a = dict(arch)
  h = _norm(x, final_norm, a["norm_eps"])
  # Tied embeddings unembed with the (V, d) embedding table itself.
  eq = "sd,vd->sv" if a["tie_embeddings"] else "sd,dv->sv"
  return _wmm(eq, h, w_out, 1, fp8)


class Reference:
  """Prefill a prompt once, then score any continuation of it.

  ``arch``: d_model, n_heads, n_kv_heads, head_dim, d_ff, vocab,
  rope_theta, norm_eps, parallel_block, tie_embeddings, n_layers.
  ``weights`` draws them from the seed: ``top()`` gives embed,
  final_norm and unembed, ``layer(l)`` one layer's tree; each layer is
  drawn when it runs, so only one is resident at a time."""

  def __init__(self, arch: Dict, weights, fp8: bool = False):
    self.arch = tuple(sorted(arch.items()))
    self.a = arch
    self.weights = weights
    self.top = weights.top()
    self.fp8 = fp8

  def _logits(self, x):
    w_out = self.top["embed" if self.a["tie_embeddings"] else "unembed"]
    return _logits(x, self.top["final_norm"], w_out, arch=self.arch,
                   fp8=self.fp8)

  def _run(self, tokens, start: int, past: List[Tuple]):
    x = self.top["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    pos = start + jnp.arange(len(tokens), dtype=jnp.int32)
    kv = []
    empty = jnp.zeros((0, self.a["n_kv_heads"], self.a["head_dim"]),
                      jnp.float32)
    for layer in range(self.a["n_layers"]):
      pk, pv = past[layer] if past else (empty, empty)
      x, k, v = _layer(x, pk, pv, self.weights.layer(layer), pos,
                       arch=self.arch, fp8=self.fp8)
      kv.append((jnp.concatenate([pk, k]), jnp.concatenate([pv, v])))
    return x, kv

  def prefill(self, prompt):
    """Keys/values of every layer, and the logits after the prompt."""
    x, kv = self._run(prompt, 0, [])
    return kv, self._logits(x[-1:])[0]

  def extend(self, kv, tokens, start: int):
    """Logits after each of ``tokens``, which follow a prefilled prompt
    of ``start`` tokens."""
    n = len(tokens)
    # One shape for every continuation up to EXTEND tokens; the padding
    # comes after them, so causal attention never lets them see it.
    pad = -n % EXTEND
    x, _ = self._run(list(tokens) + [0] * pad, start, kv)
    return self._logits(x[:n])
