"""pjit train step: microbatched grad accumulation + AdamW (+ optional
error-feedback int8 cross-pod gradient reduction).

TrainState:
  params_f32  — master weights (FSDP-sharded via TRAIN_RULES)
  opt         — Adam moments + step (same sharding: ZeRO-1/3 hybrid)
  err         — compression error feedback (only when pod-compression on)

The step consumes a *global* batch (sharded over pod x data), splits it
into ``microbatches`` slices scanned sequentially (activation memory /
overlap knob), computes bf16 forward/backward with full remat, and
applies AdamW in f32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.dist import sharding as shd
from repro.models import common as cm
from repro.models import transformer as tf
from repro.train import compression as comp
from repro.train import optimizer as opt_lib


def init_train_state(key, cfg: cm.ModelConfig, opt_cfg, *, compress=False):
  boxed = tf.init_model(key, cfg)
  params, axes = cm.split(boxed)
  params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
  state = {"params": params, "opt": opt_lib.init_opt_state(params)}
  if compress:
    state["err"] = comp.init_error_feedback(params)
  state_axes = {
      "params": axes,
      "opt": {"m": axes, "v": axes, "step": ()},
  }
  if compress:
    state_axes["err"] = axes
  return state, state_axes


def make_train_step(cfg: cm.ModelConfig, opt_cfg: opt_lib.OptConfig, *,
                    microbatches: int = 1, compress_pods: bool = False,
                    mesh=None, causal_skip: bool = False, param_axes=None):
  """Returns train_step(state, batch) -> (state, metrics); pjit-ready.

  ``param_axes`` (the logical-axes tree) enables per-layer FSDP weight
  gathering inside the scanned blocks."""

  def grads_of(params_f32, batch):
    params_bf16 = jax.tree.map(lambda p: p.astype(cfg.dtype), params_f32)

    def loss_fn(p, mb):
      loss, metrics = tf.forward_loss(
          p, cfg, mb["tokens"], mb["labels"], mb.get("frontend_embeds"),
          causal_skip=causal_skip, param_axes=param_axes)
      return loss, metrics

    if microbatches == 1:
      (loss, metrics), grads = jax.value_and_grad(
          loss_fn, has_aux=True)(params_bf16, batch)
      return loss, metrics, grads

    def split_mb(x):
      B = x.shape[0]
      return x.reshape(microbatches, B // microbatches, *x.shape[1:])

    mbs = jax.tree.map(split_mb, batch)

    def acc_fn(carry, mb):
      gacc, lacc = carry
      (loss, metrics), g = jax.value_and_grad(
          loss_fn, has_aux=True)(params_bf16, mb)
      gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gacc, g)
      return (gacc, lacc + loss), metrics

    g0 = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params_bf16)
    (grads, loss), metrics = jax.lax.scan(acc_fn, (g0, 0.0), mbs)
    grads = jax.tree.map(lambda g: g / microbatches, grads)
    metrics = jax.tree.map(lambda m: m[-1], metrics)
    return loss / microbatches, metrics, grads

  def train_step(state, batch):
    loss, metrics, grads = grads_of(state["params"], batch)

    if compress_pods and mesh is not None and "pod" in mesh.shape:
      # Cross-pod reduction by hand (int8 + error feedback); within-pod
      # reductions stay in GSPMD.  shard_map manual only on 'pod'.
      def red(g, e):
        return comp.compressed_pod_psum(g, e, "pod")

      from jax.sharding import PartitionSpec as P  # noqa: PLC0415
      spec = jax.tree.map(lambda _: P(), grads)
      grads, new_err = jax.shard_map(
          red, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
          check_vma=False, axis_names=frozenset({"pod"}),
      )(grads, state["err"])
      state = {**state, "err": new_err}
      grads = jax.tree.map(lambda g: g / mesh.shape["pod"], grads)

    new_params, new_opt, om = opt_lib.adamw_update(
        grads, state["opt"], state["params"], opt_cfg)
    new_state = {**state, "params": new_params, "opt": new_opt}
    out_metrics = {"loss": loss, **metrics, **om}
    return new_state, out_metrics

  return train_step


def state_shardings(state_axes, mesh, state_shapes):
  return shd.tree_shardings(state_axes, mesh, shd.TRAIN_RULES, state_shapes)
