"""Fused synopsis score + stage-1 attention Pallas kernel.

Algorithm 1 lines 1 + 4 in ONE pass over the centroid tables: each grid
step loads one (block_m, D) tile of ``k_syn``/``v_syn``, computes the
(G, block_m) centroid logits once on the MXU, and uses them TWICE —

  * reduced over the GQA group by max -> the correlation scores ``c_i``
    that feed ``lax.top_k`` ranking (uncapped, scale-only: ranking is
    invariant under the monotone softcap);
  * softcapped + count-bias -> online-softmax partials of the stage-1
    synopsis attention over ALL centroids.

The unfused path reads ``k_syn`` twice (score kernel + flash decode) and
``v_syn`` once in a separate kernel launch; this kernel reads each exactly
once and shares the logit matmul.  The selected-cluster mask cannot be
applied here (selection *depends* on the scores this kernel emits), so the
partials are over all centroids with the ``log(count)`` bias; the
refinement kernel subtracts the selected centroids' terms exactly
(decremental masking — see block_gather_attention's fused epilogue and
EXPERIMENTS.md §Fusion).

Tiling: grid (B, Hkv, M/block_m); online-softmax state lives in VMEM
scratch across the sequential last grid axis, flushing (o, m, l) at the
final step.  ``cbias`` is the precomputed ``log(max(counts, 1))`` (B, M).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import apply_softcap

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, cb_ref, *rest, sm_scale: float,
            cap: Optional[float], num_m_blocks: int, has_scale: bool):
  it = iter(rest)
  ks_ref = vs_ref = None
  if has_scale:                 # quantized synopsis (DESIGN.md §15)
    ks_ref, vs_ref = next(it), next(it)
  s_ref, o_ref, m_ref, l_ref, acc, m_s, l_s = it
  m_idx = pl.program_id(2)

  @pl.when(m_idx == 0)
  def _init():
    acc[...] = jnp.zeros_like(acc)
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)

  q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
  k = k_ref[0, 0].astype(jnp.float32)               # (bm, D)
  v = v_ref[0, 0].astype(jnp.float32)               # (bm, D)

  logits = jax.lax.dot_general(                     # (G, bm) — computed ONCE
      q, k, (((1,), (1,)), ((), ())),
      preferred_element_type=jnp.float32)
  if has_scale:
    # Dequantize in the accumulator: the per-centroid k-scale (>= 0, so
    # the score ranking is preserved) multiplies the raw logits; k_syn
    # itself is never materialized in f32.
    logits = logits * ks_ref[0, 0].astype(jnp.float32)
  logits = logits * sm_scale

  # Use 1: correlation scores (uncapped — softcap is monotone, ranking
  # unchanged; matches ref.synopsis_score_ref).
  s_ref[0, 0] = jnp.max(logits, axis=0, keepdims=True)   # (1, bm)

  # Use 2: stage-1 attention partials over the same tile.
  logits = apply_softcap(logits, cap)
  logits = logits + cb_ref[0].astype(jnp.float32)   # (1, bm) bias row

  m_prev = m_s[...]                                 # (G, 1)
  m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
  p = jnp.exp(logits - m_new)
  alpha = jnp.exp(m_prev - m_new)
  l_new = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
  # v-scale weights p entering the p·v matmul; l stays unscaled (the
  # softmax weights are scale-free — only the value rows are quantized).
  pv = p if not has_scale else p * vs_ref[0, 0].astype(jnp.float32)
  acc[...] = acc[...] * alpha + jax.lax.dot_general(
      pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
  m_s[...] = m_new
  l_s[...] = l_new

  @pl.when(m_idx == num_m_blocks - 1)
  def _flush():
    l_fin = l_s[...]
    o_ref[0, 0] = acc[...] / jnp.maximum(l_fin, 1e-30)
    m_ref[0, 0] = m_s[...]
    l_ref[0, 0] = l_fin


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "cap", "block_m", "interpret"))
def fused_synopsis_score_attention(
    q: jax.Array,        # (B, H, D)
    k_syn: jax.Array,    # (B, Hkv, M, D) centroid keys
    v_syn: jax.Array,    # (B, Hkv, M, D) centroid values
    cbias: jax.Array,    # (B, M) f32 log(count) bias (additive, log-space)
    *,
    sm_scale: float = 1.0,
    cap: Optional[float] = None,
    block_m: int = 512,
    k_scale: Optional[jax.Array] = None,   # (B, Hkv, M) per-centroid-row
    v_scale: Optional[jax.Array] = None,   # dequant scales (DESIGN.md §15)
    interpret: bool = False,
):
  """Returns (scores (B,Hkv,M) f32, o (B,H,D) f32, m (B,H), l (B,H))."""
  B, H, D = q.shape
  _, Hkv, M, _ = k_syn.shape
  G = H // Hkv
  assert H == Hkv * G and k_syn.shape == v_syn.shape
  has_scale = k_scale is not None
  block_m = min(block_m, M)
  if M % block_m != 0:          # ragged centroid table: one whole-M tile
    block_m = M
  nm = M // block_m

  # Mosaic tiling: every block spans whole trailing dims or (8, 128)
  # multiples, so the GQA group and the per-centroid rows get their own
  # axes — q (B, Hkv, G, D), bias/scale/score rows (.., 1, M), m/l
  # (B, Hkv, G, 1) — reshaped back after the call.
  in_specs = [
      pl.BlockSpec((1, 1, G, D), lambda b, h, m: (b, h, 0, 0)),
      pl.BlockSpec((1, 1, block_m, D), lambda b, h, m: (b, h, m, 0)),
      pl.BlockSpec((1, 1, block_m, D), lambda b, h, m: (b, h, m, 0)),
      pl.BlockSpec((1, 1, block_m), lambda b, h, m: (b, 0, m)),
  ]
  args = [q.reshape(B, Hkv, G, D), k_syn, v_syn,
          cbias.astype(jnp.float32).reshape(B, 1, M)]
  row_spec = pl.BlockSpec((1, 1, 1, block_m), lambda b, h, m: (b, h, 0, m))
  if has_scale:
    in_specs += [row_spec, row_spec]
    args += [k_scale.astype(jnp.float32).reshape(B, Hkv, 1, M),
             v_scale.astype(jnp.float32).reshape(B, Hkv, 1, M)]
  group_spec = pl.BlockSpec((1, 1, G, D), lambda b, h, m: (b, h, 0, 0))
  stat_spec = pl.BlockSpec((1, 1, G, 1), lambda b, h, m: (b, h, 0, 0))

  fn = pl.pallas_call(
      functools.partial(_kernel, sm_scale=sm_scale, cap=cap,
                        num_m_blocks=nm, has_scale=has_scale),
      grid=(B, Hkv, nm),
      in_specs=in_specs,
      out_specs=[row_spec, group_spec, stat_spec, stat_spec],
      out_shape=[
          jax.ShapeDtypeStruct((B, Hkv, 1, M), jnp.float32),
          jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
          jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
          jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
      ],
      scratch_shapes=[
          pltpu.VMEM((G, D), jnp.float32),
          pltpu.VMEM((G, 1), jnp.float32),
          pltpu.VMEM((G, 1), jnp.float32),
      ],
      interpret=interpret,
      name="fused_synopsis_score_attention",
  )
  scores, o, m, l = fn(*args)
  return scores.reshape(B, Hkv, M), (o.reshape(B, H, D), m.reshape(B, H),
                                     l.reshape(B, H))
