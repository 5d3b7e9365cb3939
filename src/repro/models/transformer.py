"""Unified decoder model covering all 10 assigned architectures.

Layers are grouped into *super-blocks* following ``cfg.block_pattern``
(e.g. jamba's [7x mamba, 1x attn], gemma2's [local, global]); parameters
are stacked per pattern position and the model body is one
``lax.scan`` over super-blocks with full remat — this keeps the lowered
HLO size O(pattern) instead of O(n_layers), which matters when compiling
60-layer x 160-expert graphs for a 512-device mesh.

Loss is computed with a sequence-chunked logsumexp so the (B, S, vocab)
logits tensor never materialises (command-r has a 256k vocab).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import gelu_mlp, rms_norm, softcap, swiglu


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_mlp(key, cfg: cm.ModelConfig) -> dict:
  d, f = cfg.d_model, cfg.d_ff
  ks = jax.random.split(key, 4)
  if cfg.mlp_type == "gelu":
    return {
        "w1": cm.param(ks[0], (d, f), ("embed", "ff")),
        "b1": cm.zeros((f,), ("ff",)),
        "w2": cm.param(ks[1], (f, d), ("ff", "embed")),
        "b2": cm.zeros((d,), ("embed",)),
    }
  return {
      "w1": cm.param(ks[0], (d, f), ("embed", "ff")),
      "w3": cm.param(ks[1], (d, f), ("embed", "ff")),
      "w2": cm.param(ks[2], (f, d), ("ff", "embed")),
  }


def _init_layer(key, cfg: cm.ModelConfig, spec: cm.LayerSpec) -> dict:
  ks = jax.random.split(key, 8)
  p = {"ln1": cm.zeros((cfg.d_model,), ("embed",))}
  if spec.kind == "attn":
    p["attn"] = (attn.init_mla(ks[0], cfg) if cfg.mla
                 else attn.init_attention(ks[0], cfg))
  else:
    p["ssm"] = ssm_lib.init_ssm(ks[0], cfg)
  if spec.cross_attn:
    p["ln_cross"] = cm.zeros((cfg.d_model,), ("embed",))
    p["cross"] = attn.init_attention(ks[1], cfg, cross=True)
  has_ffn = cfg.d_ff > 0 or (spec.use_moe and cfg.moe)
  if has_ffn and not cfg.parallel_block:
    p["ln2"] = cm.zeros((cfg.d_model,), ("embed",))
  if spec.use_moe and cfg.moe:
    p["moe"] = moe_lib.init_moe(ks[2], cfg)
    if cfg.moe.dense_parallel:
      p["mlp"] = _init_mlp(ks[3], cfg)
  elif cfg.d_ff > 0:
    p["mlp"] = _init_mlp(ks[3], cfg)
  if cfg.sandwich_norm:
    p["ln1_post"] = cm.zeros((cfg.d_model,), ("embed",))
    if has_ffn:
      p["ln2_post"] = cm.zeros((cfg.d_model,), ("embed",))
  return p


def _stack_layers(key, cfg, spec, n: int):
  """Stack n copies of one pattern position; prepend the 'layers' axis.
  ``vmap`` over the per-layer keys draws the stacked arrays directly (the
  same values a per-layer loop draws), so no per-layer copy is ever
  concatenated."""
  axes = {}

  def one(k):
    params, axes["tree"] = cm.split(_init_layer(k, cfg, spec))
    return params

  stacked = jax.vmap(one)(jax.random.split(key, n))
  return jax.tree.map(lambda v, ax: cm.Box(v, ("layers",) + ax), stacked,
                      axes["tree"])


@functools.partial(jax.jit, static_argnums=1)
def init_params(key, cfg: cm.ModelConfig):
  """Parameters in ``cfg.dtype`` from one jitted program (no axes tree).
  The f32 draws fuse with the cast, so only the ``cfg.dtype`` tree is
  ever resident on the device — at llama3-8b widths the f32 tree alone
  would fill most of a 16 GB chip."""
  params, _ = cm.split(init_model(key, cfg))
  return jax.tree.map(lambda p: p.astype(cfg.dtype), params)


def init_model(key, cfg: cm.ModelConfig):
  """Returns a Box tree (use common.split to get params + axes trees)."""
  ks = jax.random.split(key, 16)
  p = {
      "embed": cm.param(ks[0], (cfg.vocab, cfg.d_model), ("vocab", "embed"),
                        scale=1.0),
      "final_norm": cm.zeros((cfg.d_model,), ("embed",)),
      "blocks": {
          f"pos{i}": _stack_layers(ks[1 + i], cfg, spec, cfg.n_blocks)
          for i, spec in enumerate(cfg.block_pattern)
      },
  }
  if not cfg.tie_embeddings:
    p["unembed"] = cm.param(ks[12], (cfg.d_model, cfg.vocab),
                            ("embed", "vocab"))
  if cfg.frontend:
    p["frontend_proj"] = cm.param(
        ks[13], (cfg.frontend_dim, cfg.d_model), (None, "embed"))
  if cfg.encoder:
    e = cfg.encoder
    enc_cfg = _encoder_cfg(cfg)
    p["encoder"] = {
        "blocks": _stack_layers(ks[14], enc_cfg, cm.LayerSpec(), e.n_layers),
        "final_norm": cm.zeros((cfg.d_model,), ("embed",)),
    }
  return p


def _encoder_cfg(cfg: cm.ModelConfig) -> cm.ModelConfig:
  e = cfg.encoder
  import dataclasses  # noqa: PLC0415
  return dataclasses.replace(
      cfg, n_layers=e.n_layers, n_heads=e.n_heads, n_kv_heads=e.n_heads,
      d_ff=e.d_ff, moe=None, mla=None, ssm=None, encoder=None,
      block_pattern=(cm.LayerSpec(),))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _ffn(x, lp, cfg, spec):
  if spec.use_moe and cfg.moe:
    y, aux = moe_lib.moe_ffn(x, lp["moe"], cfg)
    if cfg.moe.dense_parallel:
      y = y + _dense_mlp(x, lp["mlp"], cfg)
    return y, aux
  if cfg.d_ff > 0:
    return _dense_mlp(x, lp["mlp"], cfg), 0.0
  return jnp.zeros_like(x), 0.0


def _dense_mlp(x, mp, cfg):
  if cfg.mlp_type == "gelu":
    return gelu_mlp(x, mp["w1"], mp["b1"], mp["w2"], mp["b2"])
  return swiglu(x, mp["w1"], mp["w3"], mp["w2"])


def _layer_forward(x, lp, cfg: cm.ModelConfig, spec: cm.LayerSpec,
                   positions, enc_out, causal_skip, collect_kv=False,
                   impl=None):
  """One layer: mixer (attn/ssm/cross) + ffn, pre-norm residual."""
  aux = 0.0
  kv = {}
  h = rms_norm(x, lp["ln1"], cfg.norm_eps)
  if spec.kind == "attn":
    if cfg.mla:
      mix = attn.mla_train(h, lp["attn"], cfg, positions, causal_skip,
                           return_kv=collect_kv, impl=impl)
    else:
      mix = attn.attention_train(h, lp["attn"], cfg, positions,
                                 local=spec.local, causal_skip=causal_skip,
                                 return_kv=collect_kv, impl=impl)
    if collect_kv:
      mix, (k_, v_) = mix
      kv["k"], kv["v"] = k_, v_
  else:
    mix, st = ssm_lib.ssm_forward(h, lp["ssm"], cfg)
    if collect_kv:
      kv["conv_state"], kv["ssd_state"] = st
  if cfg.sandwich_norm:
    mix = rms_norm(mix, lp["ln1_post"], cfg.norm_eps)

  if cfg.parallel_block:
    f, aux = _ffn(h, lp, cfg, spec)
    x = x + mix + f
  else:
    x = x + mix
    if spec.cross_attn:
      hc = rms_norm(x, lp["ln_cross"], cfg.norm_eps)
      cross = attn.attention_train(hc, lp["cross"], cfg, positions,
                                   enc_out=enc_out, return_kv=collect_kv)
      if collect_kv:
        cross, (ck, cv) = cross
        kv["cross_k"], kv["cross_v"] = ck, cv
      x = x + cross
    if "ln2" in lp:
      h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
      f, aux = _ffn(h2, lp, cfg, spec)
      if cfg.sandwich_norm:
        f = rms_norm(f, lp["ln2_post"], cfg.norm_eps)
      x = x + f
  x = constrain(x, ("batch", None, None))
  return x, aux, kv


def _gather_fsdp(stacked, axes):
  """Per-layer FSDP weight gather: inside the scan body, constrain each
  weight slice to be *replicated over the FSDP (data) axis* while keeping
  its TP (model) sharding — this pins GSPMD to the all-gather-weights
  plan instead of partial-contraction + activation all-reduces (400 GB/step
  on pixtral before this; see EXPERIMENTS.md §Perf)."""
  from repro.dist import sharding as shd  # noqa: PLC0415
  rules = dict(shd.current_rules() or shd.rules_dict())
  rules["embed"] = None                      # gather the FSDP dim
  def one(leaf, ax):
    return shd.constrain(leaf, ax[1:], rules=rules)   # drop 'layers'
  return jax.tree.map(one, stacked, axes, is_leaf=lambda x: False)


def _body(params_blocks, cfg, x, positions, enc_out, causal_skip,
          pattern=None, collect_kv=False, param_axes=None, impl=None):
  """Scan over super-blocks, unrolling the pattern inside each step."""
  pattern = pattern or cfg.block_pattern

  def superblock(carry, stacked):
    x, aux = carry
    if param_axes is not None:
      stacked = _gather_fsdp(stacked, param_axes)
    ys = {}
    for i, spec in enumerate(pattern):
      x, a, kv = _layer_forward(x, stacked[f"pos{i}"], cfg, spec, positions,
                                enc_out, causal_skip, collect_kv, impl)
      aux = aux + a
      for kk, vv in kv.items():
        ys.setdefault(kk, []).append(vv)
    ys = {kk: jnp.stack(vv) for kk, vv in ys.items()} if collect_kv else None
    return (x, aux), ys

  superblock = jax.checkpoint(
      superblock, policy=jax.checkpoint_policies.nothing_saveable)
  (x, aux), ys = jax.lax.scan(superblock, (x, jnp.float32(0.0)),
                              params_blocks)
  return (x, aux, ys) if collect_kv else (x, aux)


def encode(params, cfg: cm.ModelConfig, frames: jax.Array) -> jax.Array:
  """Whisper-style encoder over precomputed frame embeddings (stub
  frontend projects them to d_model; sinusoid-free, rope positions)."""
  x = jnp.einsum("btf,fd->btd", frames, params["frontend_proj"]
                 ).astype(cfg.dtype)
  T = x.shape[1]
  positions = jnp.arange(T)
  enc_cfg = _encoder_cfg(cfg)
  # Bidirectional: reuse attention_train with cross path (enc_out=x itself
  # gives full non-causal attention over the source).
  def superblock(carry, stacked):
    x, _ = carry
    h = rms_norm(x, stacked["ln1"], cfg.norm_eps)
    mix = attn.attention_train(h, stacked["attn"], enc_cfg,
                               positions, enc_out=h)
    x = x + mix
    h2 = rms_norm(x, stacked["ln2"], cfg.norm_eps)
    f, _ = _ffn(h2, stacked, enc_cfg, cm.LayerSpec())
    return (x + f, 0.0), None

  superblock = jax.checkpoint(
      superblock, policy=jax.checkpoint_policies.nothing_saveable)
  (x, _), _ = jax.lax.scan(superblock, (x, 0.0), params["encoder"]["blocks"])
  return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)


def embed_tokens(params, cfg, tokens, frontend_embeds=None):
  x = params["embed"][tokens].astype(cfg.dtype)
  if cfg.scale_embed:
    x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
  if cfg.frontend == "vision_stub" and frontend_embeds is not None:
    prefix = jnp.einsum("bpf,fd->bpd", frontend_embeds,
                        params["frontend_proj"]).astype(cfg.dtype)
    x = jnp.concatenate([prefix, x], axis=1)
  return constrain(x, ("batch", None, None))


def hidden_states(params, cfg: cm.ModelConfig, tokens: jax.Array,
                  frontend_embeds=None, causal_skip: bool = False,
                  collect_kv: bool = False, param_axes=None, impl=None):
  """Token ids -> final hidden states (B, S, d) + moe aux loss.

  ``impl`` selects the causal-attention implementation for forward-only
  (prefill) passes — see ``attention.causal_mix``; ``None`` keeps the
  remat'd training path."""
  enc_out = None
  if cfg.encoder is not None and frontend_embeds is not None:
    enc_out = encode(params, cfg, frontend_embeds)
  x = embed_tokens(params, cfg, tokens,
                   None if cfg.encoder else frontend_embeds)
  positions = jnp.arange(x.shape[1])
  out = _body(params["blocks"], cfg, x, positions, enc_out, causal_skip,
              collect_kv=collect_kv,
              param_axes=param_axes["blocks"] if param_axes else None,
              impl=impl)
  if collect_kv:
    x, aux, kv = out
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux, kv
  x, aux = out
  return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def logits_fn(params, cfg, h):
  w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
  lg = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                  w.astype(jnp.float32))
  return softcap(lg, cfg.logit_softcap)


def chunked_loss(params, cfg: cm.ModelConfig, h: jax.Array,
                 labels: jax.Array, chunk: int = 1024) -> jax.Array:
  """Cross entropy without materialising (B, S, vocab) logits."""
  B, S, d = h.shape
  chunk = min(chunk, S)
  while S % chunk != 0:          # largest divisor of S at most `chunk`
    chunk -= 1
  w = params["embed"].T if cfg.tie_embeddings else params["unembed"]

  def one(i):
    hc = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, axis=1)
    lc = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
    lg = jnp.einsum("bsd,dv->bsv", hc.astype(jnp.float32),
                    w.astype(jnp.float32))
    lg = softcap(lg, cfg.logit_softcap)
    lg = constrain(lg, ("batch", None, "vocab"))
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)

  one = jax.checkpoint(one)
  total = jax.lax.map(one, jnp.arange(S // chunk))
  return jnp.sum(total) / (B * S)


def forward_loss(params, cfg, tokens, labels, frontend_embeds=None,
                 causal_skip: bool = False, param_axes=None):
  h, aux = hidden_states(params, cfg, tokens, frontend_embeds, causal_skip,
                         param_axes=param_axes)
  if cfg.frontend == "vision_stub" and frontend_embeds is not None:
    h = h[:, frontend_embeds.shape[1]:]          # loss on text positions
  loss = chunked_loss(params, cfg, h, labels)
  return loss + 0.01 * aux, {"ce": loss, "aux": aux}
