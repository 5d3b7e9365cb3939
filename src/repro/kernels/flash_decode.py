"""Flash-decode GQA attention Pallas kernel (TPU target).

One new query token per sequence attends over a per-sequence key set of
length S — either the full KV cache (exact baseline) or the synopsis
centroid table (AccuracyTrader stage 1, with ``bias = log(count)`` for
unselected clusters and ``-inf`` for selected ones).

Tiling: grid (B, Hkv, S/block_s).  Per step the kernel holds in VMEM one
query group (G, D), one KV tile (block_s, D) and f32 accumulators; the
online-softmax state persists in scratch across the sequential S-dimension
grid (TPU grids iterate the last axis innermost), flushing normalised
output + (m, l) partials at the final step.  D and block_s should be
multiples of 128 so the q @ k^T and p @ v contractions are MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import apply_softcap

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, *rest, sm_scale: float, cap,
            has_bias: bool, num_s_blocks: int):
  if has_bias:
    bias_ref, o_ref, m_ref, l_ref, acc, m_s, l_s = rest
  else:
    o_ref, m_ref, l_ref, acc, m_s, l_s = rest
    bias_ref = None
  s_idx = pl.program_id(2)

  @pl.when(s_idx == 0)
  def _init():
    acc[...] = jnp.zeros_like(acc)
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)

  q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
  k = k_ref[0, 0].astype(jnp.float32)               # (bs, D)
  v = v_ref[0, 0].astype(jnp.float32)               # (bs, D)

  logits = jax.lax.dot_general(                     # (G, bs) on the MXU
      q, k, (((1,), (1,)), ((), ())),
      preferred_element_type=jnp.float32) * sm_scale
  logits = apply_softcap(logits, cap)
  if bias_ref is not None:
    logits = logits + bias_ref[0, 0].astype(jnp.float32)   # (1, bs) row

  m_prev = m_s[...]                                 # (G, 1)
  m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
  p = jnp.exp(logits - m_new)                       # (G, bs)
  alpha = jnp.exp(m_prev - m_new)                   # (G, 1)
  l_new = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
  acc[...] = acc[...] * alpha + jax.lax.dot_general(
      p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
  m_s[...] = m_new
  l_s[...] = l_new

  @pl.when(s_idx == num_s_blocks - 1)
  def _flush():
    l_fin = l_s[...]
    o_ref[0, 0] = (acc[...] / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
    m_ref[0, 0] = m_s[...]
    l_ref[0, 0] = l_fin


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "cap", "block_s", "interpret"))
def flash_decode(
    q: jax.Array,                 # (B, H, D)
    k: jax.Array,                 # (B, Hkv, S, D)
    v: jax.Array,                 # (B, Hkv, S, D)
    bias: jax.Array | None = None,  # (B, Hkv, S) additive log-space bias
    *,
    sm_scale: float = 1.0,
    cap: float | None = None,     # attention softcap (pre-bias)
    block_s: int = 512,
    interpret: bool = False,
):
  """Returns partials (out (B,H,D), m (B,H), l (B,H))."""
  B, H, D = q.shape
  _, Hkv, S, _ = k.shape
  G = H // Hkv
  assert H == Hkv * G and k.shape == v.shape
  block_s = min(block_s, S)
  assert S % block_s == 0, (S, block_s)
  ns = S // block_s

  grid = (B, Hkv, ns)
  # Mosaic tiling: q/o as (B, Hkv, G, D), m/l as (B, Hkv, G, 1), the bias
  # as (B, Hkv, 1, S) rows — every block spans whole trailing dims or
  # (8, 128) multiples; reshaped back after the call.
  head = lambda b, h, s: (b, h, 0, 0)
  in_specs = [
      pl.BlockSpec((1, 1, G, D), head),
      pl.BlockSpec((1, 1, block_s, D), lambda b, h, s: (b, h, s, 0)),
      pl.BlockSpec((1, 1, block_s, D), lambda b, h, s: (b, h, s, 0)),
  ]
  args = [q.reshape(B, Hkv, G, D), k, v]
  if bias is not None:
    in_specs.append(pl.BlockSpec((1, 1, 1, block_s),
                                 lambda b, h, s: (b, h, 0, s)))
    args.append(bias.reshape(B, Hkv, 1, S))

  # Partials stay f32 regardless of input dtype: they feed merge_partials
  # (self-KV, shard compose) and rounding mid-merge would accumulate.
  out_shape = [
      jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
      jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
      jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
  ]
  stat_spec = pl.BlockSpec((1, 1, G, 1), head)
  out_specs = [pl.BlockSpec((1, 1, G, D), head), stat_spec, stat_spec]
  scratch = [
      pltpu.VMEM((G, D), jnp.float32),
      pltpu.VMEM((G, 1), jnp.float32),
      pltpu.VMEM((G, 1), jnp.float32),
  ]
  fn = pl.pallas_call(
      functools.partial(_kernel, sm_scale=sm_scale, cap=cap,
                        has_bias=bias is not None, num_s_blocks=ns),
      grid=grid,
      in_specs=in_specs,
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=scratch,
      interpret=interpret,
      name="flash_decode",
  )
  out, m, l = fn(*args)
  return out.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)
