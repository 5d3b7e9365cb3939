"""Synopsis scoring Pallas kernel: correlation c_i per aggregated point.

Paper line 1 of Algorithm 1 — "process S to obtain ... c_1 to c_m".  For
attention the correlation of cluster i to the request is the centroid
logit, reduced over the GQA group's query heads by max.  The output feeds
``lax.top_k`` ranking (lines 2-3).

Tiling: grid (B, Hkv, M/block_m); each step does a (G, D) x (D, block_m)
MXU matmul and a G-way max reduce, writing one (1, 1, block_m) score tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, out_ref, *, sm_scale: float):
  q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
  k = k_ref[0, 0].astype(jnp.float32)               # (bm, D)
  logits = jax.lax.dot_general(
      q, k, (((1,), (1,)), ((), ())),
      preferred_element_type=jnp.float32) * sm_scale
  out_ref[0, 0] = jnp.max(logits, axis=0, keepdims=True)   # (1, bm)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "block_m", "interpret"))
def synopsis_score(
    q: jax.Array,        # (B, H, D)
    k_syn: jax.Array,    # (B, Hkv, M, D) centroid keys
    *,
    sm_scale: float = 1.0,
    block_m: int = 512,
    interpret: bool = False,
) -> jax.Array:
  """Returns scores (B, Hkv, M) = max over group of centroid logits."""
  B, H, D = q.shape
  _, Hkv, M, _ = k_syn.shape
  G = H // Hkv
  block_m = min(block_m, M)
  assert M % block_m == 0, (M, block_m)

  fn = pl.pallas_call(
      functools.partial(_kernel, sm_scale=sm_scale),
      grid=(B, Hkv, M // block_m),
      # Mosaic tiling: q as (B, Hkv, G, D) and scores as (B, Hkv, 1, M)
      # rows, so every block spans whole trailing dims or (8, 128)
      # multiples.
      in_specs=[
          pl.BlockSpec((1, 1, G, D), lambda b, h, m: (b, h, 0, 0)),
          pl.BlockSpec((1, 1, block_m, D), lambda b, h, m: (b, h, m, 0)),
      ],
      out_specs=pl.BlockSpec((1, 1, 1, block_m),
                             lambda b, h, m: (b, h, 0, m)),
      out_shape=jax.ShapeDtypeStruct((B, Hkv, 1, M), jnp.float32),
      interpret=interpret,
      name="synopsis_score",
  )
  return fn(q.reshape(B, Hkv, G, D), k_syn).reshape(B, Hkv, M)
