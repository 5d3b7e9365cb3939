"""Error-feedback int8 gradient compression for the cross-pod all-reduce.

Within a pod, gradients reduce over the fast `data` axis uncompressed
(GSPMD).  *Across pods* the ICI/DCN link is the scarce resource, so the
pod-axis reduction is done manually under ``shard_map`` with per-leaf int8
quantisation + local error feedback (the residual is re-added next step),
cutting cross-pod gradient bytes 4x with no bias in expectation.

This is the "gradient compression / distributed-optimization trick"
integration point; it composes with any optimizer because it happens
before ``adamw_update``.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


def _quantise(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
  scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
  q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
  return q, scale


def compressed_pod_psum(grads, err, axis_name: str = "pod"):
  """Per-leaf: q = int8(g + err); AR(q); err' = (g + err) - deq(q).

  Must run inside shard_map with ``axis_name`` manual.  Returns
  (reduced_grads, new_err).  Gradient bytes on the wire: 1 byte/param
  (+ one f32 scale per leaf) instead of 4.
  """
  def one(g, e):
    g32 = g.astype(jnp.float32) + e
    q, scale = _quantise(g32)
    deq = q.astype(jnp.float32) * scale
    new_e = g32 - deq
    # The wire transfer is the *int8* all-gather (1 byte/param/pod) plus a
    # scalar scale; dequantise-and-sum happens locally, so cross-pod bytes
    # drop 4x vs an f32 all-reduce.  (Scales differ per pod, so a plain
    # int8 psum would be invalid.)
    q_all = jax.lax.all_gather(q, axis_name)            # (npods, ...)
    s_all = jax.lax.all_gather(scale, axis_name)        # (npods,)
    summed = jnp.tensordot(s_all, q_all.astype(jnp.float32), axes=(0, 0))
    return summed, new_e

  pairs = jax.tree.map(one, grads, err)
  is2 = lambda x: isinstance(x, tuple) and len(x) == 2
  return (jax.tree.map(lambda t: t[0], pairs, is_leaf=is2),
          jax.tree.map(lambda t: t[1], pairs, is_leaf=is2))


def init_error_feedback(params) -> Any:
  return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
