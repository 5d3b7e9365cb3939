"""Fused synopsis-build (permute + segment-mean) Pallas kernel.

Paper §2.2 step 3 specialised to KV caches (DESIGN.md §6): given the
cluster-contiguous permutation produced by the clustering stage
(``repro.core.cluster``), reorder the exact cache and aggregate each
C-token cluster into its mean-centroid row — in ONE streaming pass.

The unfused XLA chain (``ref.synopsis_build_ref``) materialises the
sorted cache with ``take_along_axis`` (HBM write), then re-reads it for
the reshape-mean (HBM read) — two full passes over the cache plus the
gather's scatter traffic.  Here the cache stays in HBM and each grid step
gathers one whole cluster: the step's C permutation entries arrive in
SMEM, and C row DMAs (one per member token, all KV heads at once) pull
the rows straight into a VMEM cluster buffer.  The step writes the buffer
out as the cluster's sorted block and reduces it to the centroid rows.
Every cache row moves through VMEM exactly once.

Grid (N, M) — one cluster per step.  Only the current cluster's
permutation slice is ever resident in SMEM (the whole (N, S) table does
not fit its 1 MiB at S=32k over 8 layers).  Centroids come out in
(N, M, Hkv, D) layout so every output block spans whole trailing dims
(the Mosaic (8, 128) rule); the wrapper moves the head axis back.
``counts`` is C for every cluster by construction.

``absorb_recent`` reuses the same kernel with the identity permutation:
the recent ring buffer's R tokens become R/C new clusters appended to the
originals + centroid tables (the paper's "situation 1" incremental
update).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import quant as qt


def _kernel(perm_ref, k_hbm, v_hbm, *rest, cluster_size: int,
            quant: Optional[str], quant_kv: bool):
  it = iter(rest)
  ks_ref, vs_ref, ksyn_ref, vsyn_ref = next(it), next(it), next(it), next(it)
  kss_ref = vss_ref = kvs_ref = vvs_ref = None
  if quant:                     # per-centroid synopsis scales (§15)
    kss_ref, vss_ref = next(it), next(it)
    if quant_kv:                # per-cluster-block sorted-KV scales
      kvs_ref, vvs_ref = next(it), next(it)
  kbuf, vbuf, sem = next(it), next(it), next(it)
  n = pl.program_id(0)

  def _copies(c, src):
    return (pltpu.make_async_copy(k_hbm.at[n, pl.ds(src, 1)],
                                  kbuf.at[pl.ds(c, 1)], sem.at[0]),
            pltpu.make_async_copy(v_hbm.at[n, pl.ds(src, 1)],
                                  vbuf.at[pl.ds(c, 1)], sem.at[1]))

  def _start(c, carry):
    for cp in _copies(c, perm_ref[0, 0, 0, c]):
      cp.start()
    return carry

  def _wait(c, carry):
    for cp in _copies(c, 0):    # same byte count as every started copy
      cp.wait()
    return carry

  jax.lax.fori_loop(0, cluster_size, _start, 0)
  jax.lax.fori_loop(0, cluster_size, _wait, 0)

  kb = jnp.swapaxes(kbuf[...].astype(jnp.float32), 0, 1)   # (Hkv, C, D)
  vb = jnp.swapaxes(vbuf[...].astype(jnp.float32), 0, 1)
  inv = jnp.float32(1.0 / cluster_size)
  kmean = jnp.sum(kb, axis=1) * inv                   # (Hkv, D)
  vmean = jnp.sum(vb, axis=1) * inv

  def _q(x, s_ref, axes):
    # Quantize from f32: scale = amax/qmax over ``axes`` (one scale per
    # head's centroid row or cluster block), the same deterministic
    # round the XLA reference uses.
    amax = jnp.abs(x)
    for ax in reversed(axes):   # one axis at a time (Mosaic reductions)
      amax = jnp.max(amax, axis=ax, keepdims=True)
    scale = amax / qt.qmax(quant)
    s_ref[0, 0] = scale.reshape(scale.shape[0], 1)
    inv_s = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    return qt.encode_scaled(x * inv_s, quant)

  if quant:
    ksyn_ref[0, 0] = _q(kmean, kss_ref, (1,))
    vsyn_ref[0, 0] = _q(vmean, vss_ref, (1,))
  else:
    ksyn_ref[0, 0] = kmean.astype(ksyn_ref.dtype)
    vsyn_ref[0, 0] = vmean.astype(vsyn_ref.dtype)
  if quant_kv:
    ks_ref[0] = _q(kb, kvs_ref, (1, 2))
    vs_ref[0] = _q(vb, vvs_ref, (1, 2))
  else:
    ks_ref[0] = kb.astype(ks_ref.dtype)               # permuted cluster
    vs_ref[0] = vb.astype(vs_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("cluster_size", "quant", "interpret"))
def segment_build(
    k: jax.Array,          # (N, Hkv, S, D) exact cache, flat leading dims
    v: jax.Array,          # (N, Hkv, S, D)
    perm: jax.Array,       # (N, S) int32: row s of the output reads
                           # source row perm[n, s]; cluster m owns rows
                           # [m*C, (m+1)*C)
    *,
    cluster_size: int,
    quant: Optional[str] = None,   # qconfig spec ("int8", "int8+kv", ...)
    interpret: bool = False,
) -> Union[Tuple[jax.Array, ...], dict]:
  """Returns (k_sorted, v_sorted, k_syn, v_syn, counts (N, M) f32).

  With ``quant`` the same streaming pass also emits the quantized arenas
  + scales (DESIGN.md §15) and returns the arena dict instead: centroids
  are quantized from the f32 cluster mean (one scale per centroid row);
  with the ``+kv`` specs the gathered cluster block is quantized whole
  (one scale per cluster block), so no f32 sorted copy ever lands in
  HBM."""
  N, Hkv, S, D = k.shape
  C = cluster_size
  assert S % C == 0, (S, C)
  M = S // C
  qc = qt.parse_qconfig(quant)
  qdt = qt.qdtype(qc.kind) if qc.enabled else None

  sorted_spec = pl.BlockSpec((1, Hkv, C, D), lambda n, m: (n, 0, m, 0))
  syn_spec = pl.BlockSpec((1, 1, Hkv, D), lambda n, m: (n, m, 0, 0))
  scale_spec = pl.BlockSpec((1, 1, Hkv, 1), lambda n, m: (n, m, 0, 0))
  out_specs = [sorted_spec, sorted_spec, syn_spec, syn_spec]
  out_shape = [
      jax.ShapeDtypeStruct((N, Hkv, S, D), qdt if qc.sorted_kv else k.dtype),
      jax.ShapeDtypeStruct((N, Hkv, S, D), qdt if qc.sorted_kv else v.dtype),
      jax.ShapeDtypeStruct((N, M, Hkv, D), qdt if qc.enabled else k.dtype),
      jax.ShapeDtypeStruct((N, M, Hkv, D), qdt if qc.enabled else v.dtype),
  ]
  n_scales = (2 + 2 * qc.sorted_kv) if qc.enabled else 0
  out_specs += [scale_spec] * n_scales
  out_shape += [jax.ShapeDtypeStruct((N, M, Hkv, 1), jnp.float32)] * n_scales

  fn = pl.pallas_call(
      functools.partial(_kernel, cluster_size=C,
                        quant=qc.kind if qc.enabled else None,
                        quant_kv=qc.sorted_kv),
      grid=(N, M),
      in_specs=[
          pl.BlockSpec((1, 1, 1, C), lambda n, m: (n, m, 0, 0),
                       memory_space=pltpu.SMEM),
          pl.BlockSpec(memory_space=pl.ANY),
          pl.BlockSpec(memory_space=pl.ANY),
      ],
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=[
          pltpu.VMEM((C, Hkv, D), k.dtype),
          pltpu.VMEM((C, Hkv, D), v.dtype),
          pltpu.SemaphoreType.DMA((2,)),
      ],
      interpret=interpret,
      name="segment_build",
  )
  outs = fn(perm.astype(jnp.int32).reshape(N, M, 1, C),
            jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
  heads_first = lambda x: jnp.swapaxes(x, 1, 2)       # (N, M, Hkv, ..) ->
  ks, vs = outs[0], outs[1]                           # (N, Hkv, M, ..)
  ksyn, vsyn = heads_first(outs[2]), heads_first(outs[3])
  counts = jnp.full((N, M), float(C), jnp.float32)
  if not qc.enabled:
    return ks, vs, ksyn, vsyn, counts
  scales = [heads_first(x)[..., 0] for x in outs[4:]]
  res = {"k": ks, "v": vs, "k_syn": ksyn, "v_syn": vsyn, "counts": counts,
         "k_syn_scale": scales[0], "v_syn_scale": scales[1]}
  if qc.sorted_kv:
    res["k_scale"], res["v_scale"] = scales[2], scales[3]
  return res
