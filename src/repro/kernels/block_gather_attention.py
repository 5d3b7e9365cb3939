"""Block-gather (cluster-sparse) flash attention Pallas kernel.

AccuracyTrader stage 2: exact attention over the *original* tokens of the
top-``i_max`` ranked clusters only.  The KV cache is stored
cluster-contiguous (cluster c = rows [c*C, (c+1)*C)), so "gather a
cluster" is an aligned block dynamic-slice — this is the index-file
adaptation that makes refinement TPU-friendly.

The selected cluster ids are **scalar-prefetched** (SMEM) so the BlockSpec
``index_map`` can steer each grid step's HBM->VMEM DMA to the right
cluster block: grid (B, Hkv, I); step (b, h, i) pulls K/V block
``selected[b, h, i]``.  Padded entries (id < 0) are clamped to block 0 and
masked with -inf inside the kernel.

Fused epilogue (the serving path) — two optional extensions run inside the
same grid/scratch, eliminating the separate ``_merge`` passes the serve
step used to do:

  * **decrement** ``(k_sel, v_sel, sel_bias)``: the first grid step loads
    the I selected clusters' centroid rows at once and starts the
    accumulator with their terms at *negative* weight
    ``-exp(softcap(q.k_syn)*scale + log count - m)``.  Stage 1
    (fused_synopsis) emits partials over ALL centroids (selection isn't
    known yet there); this subtraction removes exactly the selected
    centroids' terms, so ``merge(stage1, stage2)`` equals the masked-bias
    reference.  Per cluster the net mass (tokens - centroid) is >= 0 by
    Jensen when centroid = mean and no softcap (with softcap it may dip
    negative, which the signed merge handles); the flush guards the
    divide for degenerate/cancelled clusters either way.
  * **extras** ``(extras_k, extras_v, extras_bias)``: one trailing grid
    step accumulates the recent-ring-buffer tokens and the new token's
    self-KV (concatenated + padded outside; validity via the (B, E) bias).

Index maps are clamped so the inactive input keeps its previous block
index on each step — Pallas elides the re-fetch, so the epilogue costs
one small DMA, not a second pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import apply_softcap as _cap

NEG_INF = -1e30


def _kernel(sel_ref, q_ref, k_ref, v_ref, *rest, sm_scale: float,
            cap: Optional[float], num_i: int, num_steps: int,
            has_dec: bool, has_ext: bool, has_kq: bool):
  it = iter(rest)
  ksc_ref = vsc_ref = None
  if has_kq:                    # quantized sorted KV (DESIGN.md §15)
    ksc_ref, vsc_ref = next(it), next(it)
  kc_ref = vc_ref = cb_ref = ke_ref = ve_ref = eb_ref = None
  if has_dec:
    kc_ref, vc_ref, cb_ref = next(it), next(it), next(it)
  if has_ext:
    ke_ref, ve_ref, eb_ref = next(it), next(it), next(it)
  o_ref, m_ref, l_ref, acc, m_s, l_s = it

  b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
  q = q_ref[0, 0].astype(jnp.float32)               # (G, D)

  @pl.when(j == 0)
  def _init():
    if not has_dec:
      acc[...] = jnp.zeros_like(acc)
      m_s[...] = jnp.full_like(m_s, NEG_INF)
      l_s[...] = jnp.zeros_like(l_s)
      return
    # Decrement, once for all I selected clusters: their centroid rows'
    # stage-1 terms start the accumulator with negative weight.  Invalid
    # entries carry a NEG_INF bias (zero weight once a real logit lands).
    kc = kc_ref[0, 0].astype(jnp.float32)           # (I, D) centroid rows
    vc = vc_ref[0, 0].astype(jnp.float32)
    s_c = _cap(jax.lax.dot_general(
        q, kc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale, cap)
    s_c = s_c + cb_ref[0, 0].astype(jnp.float32)    # (G, I)
    m0 = jnp.max(s_c, axis=-1, keepdims=True)
    p_c = jnp.exp(s_c - m0)
    acc[...] = -jax.lax.dot_general(
        p_c, vc, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m0
    l_s[...] = -jnp.sum(p_c, axis=-1, keepdims=True)

  def _accumulate(logits, v, pv_scale=None):
    m_prev = m_s[...]                               # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p if pv_scale is None else p * pv_scale
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

  def _lane(ref, jc):
    # This step's per-cluster scale out of the (1, I) row: (1, 1).
    row = ref[0, 0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == jc, row, 0.0), axis=-1, keepdims=True)

  @pl.when(j < num_i)
  def _cluster():
    jc = jnp.minimum(j, num_i - 1)
    valid = sel_ref[b, h, jc] >= 0
    k = k_ref[0, 0].astype(jnp.float32)             # (C, D)
    v = v_ref[0, 0].astype(jnp.float32)
    raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if has_kq:
      # Per-cluster scalar dequant folded into the logits: this step's
      # whole (C, D) block shares one scale, so it multiplies through
      # AFTER the matmul (never a materialized f32 block).
      raw = raw * _lane(ksc_ref, jc)
    logits = _cap(raw * sm_scale, cap)
    logits = jnp.where(valid, logits, NEG_INF)      # mask padded clusters
    _accumulate(logits, v, _lane(vsc_ref, jc) if has_kq else None)

  if has_ext:
    @pl.when(j == num_i)
    def _extras():
      ke = ke_ref[0, 0].astype(jnp.float32)         # (E, D)
      ve = ve_ref[0, 0].astype(jnp.float32)
      logits = _cap(jax.lax.dot_general(
          q, ke, (((1,), (1,)), ((), ())),
          preferred_element_type=jnp.float32) * sm_scale, cap)
      _accumulate(logits + eb_ref[0].astype(jnp.float32), ve)

  @pl.when(j == num_steps - 1)
  def _flush():
    l_fin = l_s[...]
    # The decrement can cancel a degenerate (uniform) cluster's mass to
    # ~0; keep o*l == acc finite for the downstream merge.
    safe = jnp.where(jnp.abs(l_fin) > 1e-30, l_fin, 1.0)
    o_ref[0, 0] = (acc[...] / safe).astype(o_ref.dtype)
    m_ref[0, 0] = m_s[...]
    l_ref[0, 0] = l_fin


@functools.partial(
    jax.jit,
    static_argnames=("cluster_size", "sm_scale", "cap", "interpret"))
def block_gather_attention(
    q: jax.Array,          # (B, H, D)
    k: jax.Array,          # (B, Hkv, S, D) cluster-contiguous
    v: jax.Array,          # (B, Hkv, S, D)
    selected: jax.Array,   # (B, Hkv, I) int32, -1 padded
    *,
    cluster_size: int,
    sm_scale: float = 1.0,
    cap: Optional[float] = None,
    k_sel: Optional[jax.Array] = None,        # (B, Hkv, I, D) centroid keys
    v_sel: Optional[jax.Array] = None,        # (B, Hkv, I, D)
    sel_bias: Optional[jax.Array] = None,     # (B, Hkv, I) log-count bias
    extras_k: Optional[jax.Array] = None,     # (B, Hkv, E, D)
    extras_v: Optional[jax.Array] = None,     # (B, Hkv, E, D)
    extras_bias: Optional[jax.Array] = None,  # (B, E) additive log-space
    kv_k_scale: Optional[jax.Array] = None,   # (B, Hkv, M) per-cluster
    kv_v_scale: Optional[jax.Array] = None,   # dequant scales (§15)
    interpret: bool = False,
):
  """Returns partials (out (B,H,D) f32, m (B,H), l (B,H)).

  Plain call: exact attention over the selected cluster blocks.  With the
  fused epilogue inputs it additionally subtracts the selected centroids'
  stage-1 terms and folds in the recent/self extras (see module doc).
  With ``kv_k_scale``/``kv_v_scale`` the sorted KV is quantized: the
  selected clusters' scales are gathered once into a (1, I) row, and
  grid step j multiplies lane j into the logits / the p·v weights
  (DESIGN.md §15).
  """
  B, H, D = q.shape
  _, Hkv, S, _ = k.shape
  G = H // Hkv
  C = cluster_size
  assert S % C == 0
  I = selected.shape[-1]
  has_dec = k_sel is not None
  has_ext = extras_k is not None
  has_kq = kv_k_scale is not None

  num_steps = I + (1 if has_ext else 0)
  grid = (B, Hkv, num_steps)

  def _kv_index(b, h, j, sel):
    # Padded ids (-1) are clamped to block 0; the kernel masks them with
    # -inf using the raw (unclamped) scalar value.  During the extras
    # step the previous block index is reused (no DMA).
    jc = jnp.minimum(j, I - 1)
    return (b, h, jnp.maximum(sel[b, h, jc], 0), 0)

  # Mosaic tiling: every block spans whole trailing dims or (8, 128)
  # multiples — q/o as (B, Hkv, G, D), m/l as (B, Hkv, G, 1), per-cluster
  # rows as (.., 1, I); blocks that do not move with j are fetched once
  # per (b, h).
  head = lambda b, h, j, sel: (b, h, 0, 0)
  safe = jnp.maximum(selected, 0)
  in_specs = [
      pl.BlockSpec((1, 1, G, D), head),
      pl.BlockSpec((1, 1, C, D), _kv_index),
      pl.BlockSpec((1, 1, C, D), _kv_index),
  ]
  args = [q.reshape(B, Hkv, G, D), k, v]
  row_spec = pl.BlockSpec((1, 1, 1, I), head)
  if has_kq:
    # The selected clusters' scales, gathered once: step j reads lane j.
    in_specs += [row_spec, row_spec]
    args += [jnp.take_along_axis(sc.astype(jnp.float32), safe, axis=2
                                 ).reshape(B, Hkv, 1, I)
             for sc in (kv_k_scale, kv_v_scale)]
  if has_dec:
    in_specs += [pl.BlockSpec((1, 1, I, D), head),
                 pl.BlockSpec((1, 1, I, D), head), row_spec]
    args += [k_sel, v_sel,
             jnp.where(selected >= 0, sel_bias.astype(jnp.float32),
                       NEG_INF).reshape(B, Hkv, 1, I)]
  if has_ext:
    E = extras_k.shape[2]
    in_specs += [
        pl.BlockSpec((1, 1, E, D), head),
        pl.BlockSpec((1, 1, E, D), head),
        pl.BlockSpec((1, 1, E), lambda b, h, j, sel: (b, 0, 0)),
    ]
    args += [extras_k, extras_v,
             extras_bias.astype(jnp.float32).reshape(B, 1, E)]

  stat_spec = pl.BlockSpec((1, 1, G, 1), head)
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=grid,
      in_specs=in_specs,
      out_specs=[pl.BlockSpec((1, 1, G, D), head), stat_spec, stat_spec],
      scratch_shapes=[
          pltpu.VMEM((G, D), jnp.float32),
          pltpu.VMEM((G, 1), jnp.float32),
          pltpu.VMEM((G, 1), jnp.float32),
      ],
  )
  fn = pl.pallas_call(
      functools.partial(_kernel, sm_scale=sm_scale, cap=cap, num_i=I,
                        num_steps=num_steps, has_dec=has_dec,
                        has_ext=has_ext, has_kq=has_kq),
      grid_spec=grid_spec,
      out_shape=[
          jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
          jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
          jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
      ],
      interpret=interpret,
      name="block_gather_attention",
  )
  out, m, l = fn(selected.astype(jnp.int32), *args)
  return out.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)
