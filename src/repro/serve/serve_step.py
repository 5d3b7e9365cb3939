"""Decode (serve) step: one new token against a KV cache of length S.

``mode="exact"``    — full attention over the cache (baseline; O(S)).
``mode="synopsis"`` — AccuracyTrader: stage-1 centroid scoring + initial
result, top-``i_max`` cluster refinement, exact attention over the recent
ring buffer and the new token, all merged by online-softmax partials
(O(S/C + i_max*C + R)).  This is what makes `long_500k` runnable for
attention architectures.

The attention math lives in the kernel suite (``repro.kernels.ops``)
behind an ``impl`` switch plumbed from the serve config / launcher down
through the layer scan:

  * ``impl="pallas"`` (the default on TPU) — the fused kernels:
    `fused_synopsis_score_attention` reads ``k_syn``/``v_syn`` ONCE for
    both the stage-1 scores and the count-biased partials, and
    `block_gather_attention`'s fused epilogue streams the selected
    clusters by scalar-prefetched block DMA (no materialized
    (B,Hkv,I*C,D) gather copies), subtracts the selected centroids'
    stage-1 terms (decremental masking) and folds the recent-ring +
    self-KV partials into the same grid — one merge per layer instead of
    three.
  * ``impl="xla"`` — mathematically identical pure-jnp path (CPU tests,
    multi-pod dry-run); ``impl="interpret"`` — Pallas interpreter.

The layer loop mirrors training: one ``lax.scan`` over super-blocks whose
xs are (stacked params, stacked cache slices); only *changed* state (SSM
states, per-layer KV deltas) is emitted as ys, so the big caches are
read-only inside the step (no 2x cache live range at compile).

Sharding (SERVE_RULES / LONG_RULES): cache seq axes shard over `model`
(and `data` for long_500k) — each shard is one paper "component"; the
partial-merge all-reduces are the result composer.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.kernels import ops
from repro.models import attention as attn_lib
from repro.models import common as cm
from repro.models import ssm as ssm_lib
from repro.models import transformer as tf
from repro.models.layers import einsum, rms_norm, softcap

NEG_INF = -1e30

# Canonical impl resolution lives with the kernel suite; re-exported here
# for the launcher and tests that historically import it from serve_step.
resolve_impl = ops.resolve_impl


def _seq_axes():
  """Mesh axes the KV cache sequence dim is sharded over (rule table)."""
  from repro.dist import sharding as shd  # noqa: PLC0415
  rules = shd.current_rules() or dict(shd.DEFAULT_RULES)
  t = rules.get("kv_seq")
  if t is None:
    return ()
  return (t,) if isinstance(t, str) else tuple(t)


# ---------------------------------------------------------------------------
# Decode attention over a layer's cache slice — thin wrappers over the
# kernel-suite ops (all partial algebra now lives in repro.kernels).
# ---------------------------------------------------------------------------

def synopsis_decode_attention(
    q: jax.Array,            # (B, H, Dk) rope'd new-token queries
    cache: Dict[str, jax.Array],   # slice for this layer (no nb/na dims)
    *,
    i_max: int,
    cluster_size: int,
    sm_scale: float,
    cap: Optional[float] = None,
    self_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    impl: str = "xla",
):
  """AccuracyTrader Algorithm 1 on a KV cache; returns (B, H, Dv).

  Quantized-arena scale leaves (DESIGN.md §15) ride along when present
  in the cache slice; absent they keep the bit-identical f32 path."""
  self_k, self_v = self_kv if self_kv is not None else (None, None)
  return ops.synopsis_cache_attention(
      q, cache["k"], cache["v"], cache["k_syn"], cache["v_syn"],
      cache["counts"], cache.get("recent_k"), cache.get("recent_v"),
      cache.get("recent_len"), self_k, self_v,
      cache.get("k_syn_scale"), cache.get("v_syn_scale"),
      cache.get("k_scale"), cache.get("v_scale"),
      i_max=i_max, cluster_size=cluster_size, sm_scale=sm_scale, cap=cap,
      impl=impl)


def sharded_synopsis_attention(
    q, cache, *, i_max, cluster_size, sm_scale, cap=None, self_kv=None,
    seq_axes=("model",), impl="xla",
):
  """AccuracyTrader decode attention with the KV cache + synopsis sharded
  over ``seq_axes`` — the paper's n-component scatter-gather, made
  explicit: every shard ("component") scores its own centroids, the
  *global* ranking comes from one small score all-gather, each shard
  refines only the selected clusters it owns, and the online-softmax merge
  of shard partials is the result composer.  Collectives per layer: one
  (B,Hkv,M) f32 all-gather + one (B,H,D+2) partials all-gather — vs. the
  GSPMD fallback which all-gathers the whole cache shard (see
  EXPERIMENTS.md §Perf iteration 1).

  The shard-local body is the same two fused kernel stages as the
  single-device path (stage-1 fused score+attention over the local
  centroids, decremental stage-2 over locally-owned selected clusters);
  the recent/self extras fold into shard 0's stage-2 launch so they are
  counted exactly once."""
  from repro.dist import sharding as shd  # noqa: PLC0415
  from jax.sharding import PartitionSpec as P  # noqa: PLC0415
  mesh = shd.current_mesh()
  axes = tuple(a for a in seq_axes if mesh is not None and a in mesh.shape)
  M = cache["k_syn"].shape[2]
  B = q.shape[0]
  nshards = 1
  for a in axes:
    nshards *= mesh.shape[a]
  if not axes or M % nshards != 0 or nshards == 1:
    return synopsis_decode_attention(
        q, cache, i_max=i_max, cluster_size=cluster_size,
        sm_scale=sm_scale, cap=cap, self_kv=self_kv, impl=impl)

  # The batch dim stays DP-sharded: it must be *manual* too, else the
  # shard_map boundary would force-replicate it (a (B,Hkv,S/16,D) gather).
  dp = tuple(a for a in ("pod", "data")
             if a in mesh.shape and a not in axes)
  dp_n = 1
  for a in dp:
    dp_n *= mesh.shape[a]
  if B % max(dp_n, 1) != 0:
    dp, dp_n = (), 1
  bspec = dp if dp else None

  manual = frozenset(axes) | frozenset(dp)
  kv_spec = P(bspec, None, axes, None)
  specs = {"k": kv_spec, "v": kv_spec, "k_syn": kv_spec, "v_syn": kv_spec,
           "counts": P(bspec, axes)}
  for name in ("k_syn_scale", "v_syn_scale", "k_scale", "v_scale"):
    if name in cache:        # quantized arena (§15): shard like counts
      specs[name] = P(bspec, None, axes)
  for name in ("recent_k", "recent_v"):
    if name in cache:
      specs[name] = P(bspec, None, None, None)
  if "recent_len" in cache:
    specs["recent_len"] = P(bspec)
  cache = {k_: cache[k_] for k_ in specs}
  M_local = M // nshards
  q_spec = P(bspec, None, None)
  self_spec = (P(bspec, None, None, None),) * 2 if self_kv is not None \
      else P()

  def body(q, cache, self_kv):
    with shd.manual_axes(manual):
      # Combined shard index along the sequence axes.
      sid = jnp.int32(0)
      for a in axes:
        sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
      k_syn = cache["k_syn"]

      syn_scales = (None if "k_syn_scale" not in cache else
                    (cache["k_syn_scale"], cache["v_syn_scale"]))
      kv_scales = (None if "k_scale" not in cache else
                   (cache["k_scale"], cache["v_scale"]))

      # Stage 1 (fused): local scores + local count-biased partials in
      # one pass; then one small all-gather for the global ranking.
      sc_local, p_syn = ops.synopsis_stage1(
          q, k_syn, cache["v_syn"], cache["counts"], sm_scale=sm_scale,
          cap=cap, impl=impl, syn_scales=syn_scales)
      sc = sc_local
      for a in reversed(axes):
        sc = jax.lax.all_gather(sc, a, axis=2, tiled=True)   # (B,Hkv,M)
      _, selected = jax.lax.top_k(sc, min(i_max, M))
      selected = selected.astype(jnp.int32)

      # Stage 2 (fused epilogue): refine only the clusters this shard
      # owns; the decrement removes their centroid terms from p_syn.
      lo = sid * M_local
      sel_rel = selected - lo
      mine = (sel_rel >= 0) & (sel_rel < M_local)
      sel_local = jnp.where(mine, sel_rel, -1)

      extras = ops.build_extras(
          cache.get("recent_k"), cache.get("recent_v"),
          cache.get("recent_len"), self_kv)
      if extras is not None:
        ek, ev, eb = extras
        eb = jnp.where(sid == 0, eb, NEG_INF)   # count extras once
        extras = (ek, ev, eb)
      p_ref = ops.refine_stage2(
          q, cache["k"], cache["v"], sel_local, k_syn, cache["v_syn"],
          cache["counts"], cluster_size=cluster_size, sm_scale=sm_scale,
          cap=cap, impl=impl, extras=extras, syn_scales=syn_scales,
          kv_scales=kv_scales)
      part = ops.merge_partials(p_syn, p_ref)

      # Compose shard partials (the paper's result composer).
      o, m_, l_ = part
      gathered = [o[None], m_[None], l_[None]]
      for a in reversed(axes):
        gathered = [jax.lax.all_gather(g, a, axis=0, tiled=True)
                    for g in gathered]
      og, mg, lg = gathered
      acc = (og[0], mg[0], lg[0])
      for i in range(1, og.shape[0]):
        acc = ops.merge_partials(acc, (og[i], mg[i], lg[i]))
      return acc[0]

  return jax.shard_map(
      body, mesh=mesh, in_specs=(q_spec, specs, self_spec),
      out_specs=q_spec if dp else P(),
      axis_names=manual, check_vma=False,
  )(q, cache, self_kv)


def exact_decode_attention(q, k, v, *, sm_scale, cap=None, self_kv=None,
                           window: Optional[int] = None, impl="xla"):
  if window is not None and window < k.shape[2]:
    k = k[:, :, -window:]
    v = v[:, :, -window:]
  out = ops.decode_partials(q, k, v, sm_scale=sm_scale, cap=cap, impl=impl)
  if self_kv is not None:
    # One-token self partial: always the jnp path (a (B,Hkv,1,D) einsum
    # is cheaper than a kernel launch and tile-shape agnostic).
    out = ops.merge_partials(
        out, ops.decode_partials(q, self_kv[0], self_kv[1],
                                 sm_scale=sm_scale, cap=cap, impl="xla"))
  return out[0]


# ---------------------------------------------------------------------------
# Per-layer decode
# ---------------------------------------------------------------------------

def _attn_decode_layer(x, lp, cfg: cm.ModelConfig, spec, cache_sl, pos,
                       mode, i_max, impl, attention_fn=None):
  """x (B,1,d); cache_sl: this layer's cache slice.
  Returns (y, delta, aux) — ``aux`` is None unless an ``attention_fn``
  override (the cluster tier, DESIGN.md §9) reports per-component
  telemetry to thread out of the layer scan."""
  B = x.shape[0]
  aux = None

  def synopsis_attn(q, csl, *, sm_scale, cap=None, self_kv=None):
    nonlocal aux
    if attention_fn is None:
      return sharded_synopsis_attention(
          q, csl, i_max=i_max, cluster_size=cfg.synopsis.cluster_size,
          sm_scale=sm_scale, cap=cap, self_kv=self_kv,
          seq_axes=_seq_axes(), impl=impl)
    ctx, aux = attention_fn(
        q, csl, i_max=i_max, cluster_size=cfg.synopsis.cluster_size,
        sm_scale=sm_scale, cap=cap, self_kv=self_kv, impl=impl)
    return ctx
  positions = pos[:, None]                                    # (B,1)
  if cfg.mla:
    m = cfg.mla
    q_nope, q_pe = attn_lib.mla_queries(x, lp, cfg, positions)
    c_kv, k_pe = attn_lib.mla_latent(x, lp, cfg, positions)
    # Absorbed: q_lat[h] = q_nope[h] @ wk_b[:,h,:]^T  -> latent space.
    q_lat = einsum("bshk,rhk->bshr", q_nope, lp["wk_b"])[:, 0]  # (B,H,r)
    q_eff = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1)     # (B,H,r+rope)
    lat_new = jnp.concatenate([c_kv, k_pe], axis=-1)          # (B,1,Dk)
    self_kv = (lat_new[:, None], lat_new[:, None])            # (B,1,1,Dk)
    sm_scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    if mode == "synopsis":
      ctx = synopsis_attn(q_eff, cache_sl, sm_scale=sm_scale,
                          self_kv=self_kv)
    else:
      ctx = exact_decode_attention(q_eff, cache_sl["k"], cache_sl["v"],
                                   sm_scale=sm_scale, self_kv=self_kv,
                                   impl=impl)
    # ctx is a latent-space context (B, H, r+rope); drop the rope part and
    # decompress per head via wv_b.
    ctx_lat = ctx[..., :m.kv_lora_rank]
    o = einsum("bhr,rhk->bhk", ctx_lat, lp["wv_b"])           # (B,H,v_dim)
    y = einsum("bhk,hkd->bd", o, lp["wo"])[:, None].astype(x.dtype)
    delta = (lat_new[:, None], lat_new[:, None])
  else:
    q, k_new, v_new = attn_lib.qkv(x, lp, cfg, positions)
    q = q[:, 0]                                               # (B,H,D)
    kd = jnp.moveaxis(k_new, 1, 2)                            # (B,Hkv,1,D)
    vd = jnp.moveaxis(v_new, 1, 2)
    sm_scale = cfg.hd ** -0.5
    if spec.local:
      ctx = exact_decode_attention(
          q, cache_sl["k"], cache_sl["v"], sm_scale=sm_scale,
          cap=cfg.attn_softcap, self_kv=(kd, vd),
          window=cfg.sliding_window, impl=impl)
    elif mode == "synopsis":
      ctx = synopsis_attn(q, cache_sl, sm_scale=sm_scale,
                          cap=cfg.attn_softcap, self_kv=(kd, vd))
    else:
      ctx = exact_decode_attention(
          q, cache_sl["k"], cache_sl["v"], sm_scale=sm_scale,
          cap=cfg.attn_softcap, self_kv=(kd, vd), impl=impl)
    y = attn_lib.out_proj(ctx[:, None].astype(x.dtype), lp, x.dtype)
    delta = (kd, vd)
  return y, delta, aux


def _cross_decode_layer(x, lp, cfg, cache_sl, impl):
  q = einsum("bsd,dhk->bshk", x, lp["wq"]).astype(x.dtype)
  if "bq" in lp:
    q = q + lp["bq"][None, None].astype(x.dtype)
  ctx = exact_decode_attention(q[:, 0], cache_sl["cross_k"],
                               cache_sl["cross_v"],
                               sm_scale=cfg.hd ** -0.5, impl=impl)
  return attn_lib.out_proj(ctx[:, None].astype(x.dtype), lp, x.dtype)


# ---------------------------------------------------------------------------
# Full serve step
# ---------------------------------------------------------------------------

def make_serve_step(cfg: cm.ModelConfig, *, mode: str = "exact",
                    i_max: Optional[int] = None,
                    impl: Optional[str] = None,
                    attention_fn=None):
  """Returns serve_step(params, cache, tokens) ->
  (logits (B, vocab), new_state dict with ssm/kv deltas).

  ``impl`` overrides ``cfg.synopsis.impl``; both default to "auto"
  (fused Pallas kernels on TPU, XLA reference elsewhere).

  ``attention_fn`` optionally replaces the synopsis decode attention with
  a custom scatter-gather body (the multi-component cluster tier,
  DESIGN.md §9).  It is called as ``attention_fn(q, cache_sl, i_max=...,
  cluster_size=..., sm_scale=..., cap=..., self_kv=..., impl=...)`` and
  must return ``(ctx, aux)`` where ``aux`` is a dict of small per-layer
  telemetry arrays threaded out of the scan as extra ``new_state``
  entries.  Cache keys starting with ``"fe_"`` (frontend inputs, e.g. the
  per-component gather-mode vector) are broadcast to every layer instead
  of scanned."""
  i_max = cfg.synopsis.i_max if i_max is None else i_max
  impl = resolve_impl(impl if impl is not None else cfg.synopsis.impl)
  pattern = cfg.block_pattern

  def serve_step(params, cache, tokens):
    B = tokens.shape[0]
    x = params["embed"][tokens[:, 0]][:, None].astype(cfg.dtype)   # (B,1,d)
    if cfg.scale_embed:
      x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    pos = cache["pos"]

    attn_i = [i for i, s in enumerate(pattern) if s.kind == "attn"]
    ssm_i = [i for i, s in enumerate(pattern) if s.kind == "mamba"]

    def superblock(carry, xs):
      x, = carry
      blk, csl = xs
      deltas: Dict[str, Any] = {}
      ai = si = 0
      for i, spec in enumerate(pattern):
        lp = blk[f"pos{i}"]
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if spec.kind == "attn":
          layer_cache = {kk: csl[kk][ai] for kk in csl
                         if kk not in ("conv_state", "ssd_state",
                                       "recent_len")
                         and not kk.startswith("fe_")}
          for kk in csl:
            if kk == "recent_len" or kk.startswith("fe_"):
              layer_cache[kk] = csl[kk]
          mix, delta, aux = _attn_decode_layer(h, lp["attn"], cfg, spec,
                                               layer_cache, pos, mode,
                                               i_max, impl, attention_fn)
          deltas.setdefault("k_delta", []).append(delta[0])
          deltas.setdefault("v_delta", []).append(delta[1])
          if aux:
            for ak, av in aux.items():
              deltas.setdefault(ak, []).append(av)
          ai += 1
        else:
          st = (csl["conv_state"][si], csl["ssd_state"][si])
          mix, new_st = ssm_lib.ssm_forward(h, lp["ssm"], cfg,
                                            decode_state=st)
          deltas.setdefault("conv_state", []).append(new_st[0])
          deltas.setdefault("ssd_state", []).append(new_st[1])
          si += 1
        if cfg.sandwich_norm:
          mix = rms_norm(mix, lp["ln1_post"], cfg.norm_eps)
        if cfg.parallel_block:
          f, _ = tf._ffn(h, lp, cfg, spec)
          x = x + mix + f
        else:
          x = x + mix
          if spec.cross_attn:
            hc = rms_norm(x, lp["ln_cross"], cfg.norm_eps)
            ccache = {"cross_k": csl["cross_k"][ai - 1],
                      "cross_v": csl["cross_v"][ai - 1]}
            x = x + _cross_decode_layer(hc, lp["cross"], cfg, ccache, impl)
          if "ln2" in lp:
            h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f, _ = tf._ffn(h2, lp, cfg, spec)
            if cfg.sandwich_norm:
              f = rms_norm(f, lp["ln2_post"], cfg.norm_eps)
            x = x + f
      ys = {kk: jnp.stack(vv) for kk, vv in deltas.items()}
      return (x,), ys

    cache_xs = {kk: vv for kk, vv in cache.items()
                if kk not in ("pos", "recent_len")
                and not kk.startswith("fe_")}
    (x,), ys = jax.lax.scan(
        functools.partial(_scan_body, superblock, cache, cfg),
        (x,), (params["blocks"], cache_xs))

    h = rms_norm(x, params["final_norm"], cfg.norm_eps)[:, 0]   # (B,d)
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bd,dv->bv", h.astype(jnp.float32),
                        w.astype(jnp.float32))
    logits = softcap(logits, cfg.logit_softcap)
    logits = constrain(logits, ("batch", "vocab"))
    new_state = dict(ys)
    new_state["pos"] = pos + 1
    return logits, new_state

  return serve_step


def _scan_body(superblock, cache, cfg, carry, xs):
  blk, csl = xs
  bcast = [kk for kk in cache if kk == "recent_len" or kk.startswith("fe_")]
  if bcast:
    csl = dict(csl)
    for kk in bcast:
      csl[kk] = cache[kk]
  return superblock(carry, (blk, csl))
