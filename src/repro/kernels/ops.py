"""Public jit'd ops over the Pallas kernels, with an ``impl`` switch:

  * ``impl="pallas"``     — real TPU lowering (pl.pallas_call)
  * ``impl="interpret"``  — Pallas interpreter (CPU validation)
  * ``impl="xla"``        — pure-jnp reference path, mathematically
    identical; used by the multi-pod dry-run and CPU tests (Pallas cannot
    lower to the CPU backend, and inlining the interpreter into a
    512-device SPMD program is not meaningful).

Two generations of the AccuracyTrader decode op live here:

  * :func:`synopsis_attention` — the original *unfused* composition
    (score kernel + biased flash decode + block gather + merges).  Kept
    as the benchmark baseline and the "paper algebra" oracle.
  * the **fused pipeline** — :func:`synopsis_stage1` (one pass over
    ``k_syn``/``v_syn`` emits scores AND count-biased stage-1 partials),
    ``lax.top_k``, :func:`refine_stage2` (selected clusters' tokens +
    decremental centroid masking + recent/self extras in one kernel), one
    final merge.  :func:`synopsis_cache_attention` is the end-to-end op
    the serving path calls; the sharded serve body composes the two
    stages directly around its score all-gather.

The fused pipeline reads the synopsis tables once instead of twice and
replaces the serve step's materialized (B,Hkv,I*C,D) gather copies with
scalar-prefetch-steered block DMAs on the Pallas path (the XLA impl keeps
the gather — XLA cannot express the streaming form).

The prefill half of the system lives here too (DESIGN.md §6):
:func:`prefill_attention` (flash-style causal GQA over the prompt) and
:func:`synopsis_build` (fused permute + segment-mean that turns the
prefilled cache into the synopsis) — both behind the same ``impl``
switch, called from ``serve/prefill.py`` / ``serve/synopsis_kv.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import quant as qt
from repro.kernels import ref
from repro.kernels.block_gather_attention import block_gather_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.fused_synopsis import fused_synopsis_score_attention
from repro.kernels.synopsis_build import segment_build
from repro.kernels.synopsis_score import synopsis_score

NEG_INF = ref.NEG_INF
merge_partials = ref.merge_partials


def resolve_impl(impl: Optional[str] = None) -> str:
  """"auto"/None -> Pallas kernels on TPU, XLA reference elsewhere."""
  if impl in ("pallas", "xla", "interpret"):
    return impl
  return "pallas" if jax.default_backend() == "tpu" else "xla"


def _scores(q, k_syn, sm_scale, impl):
  if impl == "xla":
    return ref.synopsis_score_ref(q, k_syn, sm_scale=sm_scale)
  return synopsis_score(q, k_syn, sm_scale=sm_scale,
                        interpret=(impl == "interpret"))


def _decode(q, k, v, bias, sm_scale, impl, block_s=512, cap=None):
  if impl == "xla":
    return ref.flash_decode_ref(q, k, v, bias, sm_scale=sm_scale, cap=cap)
  S = k.shape[2]
  block_s = min(block_s, S)
  while S % block_s != 0:       # ragged seq (e.g. whisper cross T=1500):
    block_s -= 1                # largest divisor <= block_s, not one
                                # whole-S tile that could blow VMEM
  return flash_decode(q, k, v, bias, sm_scale=sm_scale, cap=cap,
                      block_s=block_s, interpret=(impl == "interpret"))


def _gather(q, k, v, selected, cluster_size, sm_scale, impl, cap=None):
  if impl == "xla":
    return ref.block_gather_attention_ref(
        q, k, v, selected, cluster_size=cluster_size, sm_scale=sm_scale)
  return block_gather_attention(
      q, k, v, selected, cluster_size=cluster_size, sm_scale=sm_scale,
      cap=cap, interpret=(impl == "interpret"))


def count_bias(counts: jax.Array) -> jax.Array:
  """log(count) stand-in weight of an unselected cluster's centroid."""
  return jnp.log(jnp.maximum(counts, 1.0)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Prefill-side ops (DESIGN.md §6): flash prefill attention + the fused
# synopsis build that turns the prefilled cache into the synopsis.
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "cap", "window", "block_q", "block_k",
                     "impl"))
def prefill_attention(
    q: jax.Array,        # (B, S, H, D)   model layout
    k: jax.Array,        # (B, S, Hkv, D)
    v: jax.Array,        # (B, S, Hkv, D)
    *,
    sm_scale: float = 1.0,
    cap: Optional[float] = None,
    window: Optional[int] = None,
    block_q: int = 256,
    block_k: int = 256,
    impl: str = "pallas",
) -> jax.Array:
  """Causal GQA prefill attention; returns (B, S, H, D) in ``q.dtype``.

  The Pallas path block-tiles query x KV with causal/window block skip
  inside the grid; the XLA path is the chunked reference (no remat — for
  the *forward-only* prefill step; training keeps
  ``models.layers.causal_attention``)."""
  if impl == "xla":
    return ref.flash_prefill_ref(q, k, v, sm_scale=sm_scale, cap=cap,
                                 window=window)
  return flash_prefill(q, k, v, sm_scale=sm_scale, cap=cap, window=window,
                       block_q=block_q, block_k=block_k,
                       interpret=(impl == "interpret"))


@functools.partial(
    jax.jit, static_argnames=("cluster_size", "impl", "qconfig"))
def synopsis_build(
    k: jax.Array,        # (N, Hkv, S, D) exact cache, flat leading dims
    v: jax.Array,        # (N, Hkv, S, D)
    perm: jax.Array,     # (N, S) int32 cluster-contiguous permutation
    *,
    cluster_size: int,
    impl: str = "pallas",
    qconfig: Optional[str] = None,
):
  """Permute the cache cluster-contiguous AND aggregate mean centroids in
  one pass.  Returns (k_sorted, v_sorted, k_syn, v_syn, counts (N, M)),
  or — with a quantizing ``qconfig`` spec (DESIGN.md §15) — the arena
  dict including the quantized tables + per-block scales, emitted in the
  same streaming pass.

  The Pallas path streams each row through VMEM exactly once (one
  cluster per grid step, its rows DMA'd by the permutation); the XLA
  path keeps the
  take_along_axis -> reshape-mean chain (two passes + gather copies)."""
  qc = qt.parse_qconfig(qconfig)
  if impl == "xla":
    if qc.enabled:
      return ref.synopsis_build_quant_ref(
          k, v, perm, cluster_size=cluster_size, qc=qc)
    return ref.synopsis_build_ref(k, v, perm, cluster_size=cluster_size)
  return segment_build(k, v, perm, cluster_size=cluster_size,
                       quant=qc.spec if qc.enabled else None,
                       interpret=(impl == "interpret"))


# ---------------------------------------------------------------------------
# Fused pipeline stages (plain functions: they run inside the serve step's
# layer scan and the sharded body, which are already traced/jitted).
# ---------------------------------------------------------------------------

def synopsis_stage1(q, k_syn, v_syn, counts, *, sm_scale: float,
                    cap: Optional[float] = None, impl: str = "pallas",
                    valid: Optional[jax.Array] = None,
                    syn_scales: Optional[Tuple[jax.Array,
                                               jax.Array]] = None):
  """One pass over the synopsis: (scores (B,Hkv,M), partials over ALL
  centroids with log-count bias).  Selection masking happens
  decrementally in stage 2.

  ``valid`` (B, M) bool optionally masks *padding* centroid slots — the
  cluster tier pads every component's shard to a common ``m_max``
  (DESIGN.md §9).  Invalid slots get a NEG_INF bias (excluded from the
  stage-1 partial inside the kernel) and NEG_INF scores (never ranked by
  the frontend's top-k).

  ``syn_scales`` = (k_syn_scale, v_syn_scale) (B, Hkv, M) when the
  synopsis is quantized (DESIGN.md §15); dequant folds into the kernel."""
  cbias = count_bias(counts)
  if valid is not None:
    cbias = jnp.where(valid, cbias, NEG_INF)
  ks, vs = syn_scales if syn_scales is not None else (None, None)
  if impl == "xla":
    scores, part = ref.fused_synopsis_score_attention_ref(
        q, k_syn, v_syn, cbias, sm_scale=sm_scale, cap=cap,
        k_scale=ks, v_scale=vs)
  else:
    scores, part = fused_synopsis_score_attention(
        q, k_syn, v_syn, cbias, sm_scale=sm_scale, cap=cap,
        k_scale=ks, v_scale=vs, interpret=(impl == "interpret"))
  if valid is not None:
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
  return scores, part


def refine_stage2(q, k, v, selected, k_syn, v_syn, counts, *,
                  cluster_size: int, sm_scale: float,
                  cap: Optional[float] = None, impl: str = "pallas",
                  extras: Optional[Tuple[jax.Array, jax.Array,
                                         jax.Array]] = None,
                  valid: Optional[jax.Array] = None,
                  syn_scales: Optional[Tuple[jax.Array,
                                             jax.Array]] = None,
                  kv_scales: Optional[Tuple[jax.Array,
                                            jax.Array]] = None):
  """Selected clusters' original tokens (+), their centroid stage-1 terms
  (-), and the recent/self extras (+) — one fused partial.

  ``selected`` may contain -1 padding (skipped).  ``valid`` optionally
  masks entries of ``selected`` that are in-range but not owned (sharded
  path); centroid rows are gathered here (tiny: I rows, not I*C).

  Quantized arenas (DESIGN.md §15): ``syn_scales`` dequantizes the I
  gathered centroid decrement rows here (tiny — outside the kernel);
  ``kv_scales`` = (k_scale, v_scale) (B, Hkv, M) rides into the kernel,
  which reads the selected clusters' per-block scales."""
  B, H, _ = q.shape
  Hkv = k.shape[1]
  sel = selected
  if valid is not None:
    sel = jnp.where(valid, selected, -1)
  safe = jnp.maximum(sel, 0)
  k_sel = jnp.take_along_axis(k_syn, safe[..., None], axis=2)
  v_sel = jnp.take_along_axis(v_syn, safe[..., None], axis=2)
  if syn_scales is not None:
    ks, vs = syn_scales
    k_sel = k_sel.astype(jnp.float32) * jnp.take_along_axis(
        ks.astype(jnp.float32), safe, axis=2)[..., None]
    v_sel = v_sel.astype(jnp.float32) * jnp.take_along_axis(
        vs.astype(jnp.float32), safe, axis=2)[..., None]
  cb = count_bias(counts)                                     # (B, M)
  sel_bias = jnp.take_along_axis(
      jnp.broadcast_to(cb[:, None, :], (B, Hkv, cb.shape[-1])), safe,
      axis=2)
  ek, ev, eb = extras if extras is not None else (None, None, None)
  kq, vq = kv_scales if kv_scales is not None else (None, None)
  if impl == "xla":
    return ref.fused_gather_attention_ref(
        q, k, v, sel, cluster_size=cluster_size, sm_scale=sm_scale,
        cap=cap, k_sel=k_sel, v_sel=v_sel, sel_bias=sel_bias,
        extras_k=ek, extras_v=ev, extras_bias=eb,
        kv_k_scale=kq, kv_v_scale=vq)
  return block_gather_attention(
      q, k, v, sel, cluster_size=cluster_size, sm_scale=sm_scale, cap=cap,
      k_sel=k_sel, v_sel=v_sel, sel_bias=sel_bias,
      extras_k=ek, extras_v=ev, extras_bias=eb,
      kv_k_scale=kq, kv_v_scale=vq,
      interpret=(impl == "interpret"))


def build_extras(recent_k=None, recent_v=None, recent_len=None,
                 self_kv=None, *, pad_to: int = 16):
  """Concatenate the recent ring buffer and the new token's self-KV into
  one small (B, Hkv, E, D) extras block + (B, E) validity bias, padded so
  the kernel tile is sublane-aligned.  Returns None when there is
  nothing to fold in."""
  ks, vs, biases = [], [], []
  if recent_k is not None:
    B, _, R, _ = recent_k.shape
    ks.append(recent_k)
    vs.append(recent_v)
    if recent_len is None:
      biases.append(jnp.zeros((B, R), jnp.float32))
    else:
      biases.append(jnp.where(
          jnp.arange(R)[None, :] < recent_len[:, None], 0.0, NEG_INF))
  if self_kv is not None:
    k1, v1 = self_kv                                          # (B,Hkv,1,D)
    ks.append(k1)
    vs.append(v1)
    biases.append(jnp.zeros((k1.shape[0], k1.shape[2]), jnp.float32))
  if not ks:
    return None
  ke = jnp.concatenate(ks, axis=2) if len(ks) > 1 else ks[0]
  ve = jnp.concatenate(vs, axis=2) if len(vs) > 1 else vs[0]
  eb = jnp.concatenate(biases, axis=1) if len(biases) > 1 else biases[0]
  E = ke.shape[2]
  Ep = -(-E // pad_to) * pad_to
  if Ep != E:
    pad = [(0, 0), (0, 0), (0, Ep - E), (0, 0)]
    ke = jnp.pad(ke, pad)
    ve = jnp.pad(ve, pad)
    eb = jnp.pad(eb, [(0, 0), (0, Ep - E)], constant_values=NEG_INF)
  return ke, ve, eb.astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("i_max", "cluster_size", "sm_scale", "cap", "impl"))
def synopsis_cache_attention(
    q: jax.Array,        # (B, H, D)   one decode step's queries
    k: jax.Array,        # (B, Hkv, S, D) cluster-contiguous original keys
    v: jax.Array,        # (B, Hkv, S, D)
    k_syn: jax.Array,    # (B, Hkv, M, D) centroid keys
    v_syn: jax.Array,    # (B, Hkv, M, D) centroid values
    counts: jax.Array,   # (B, M)
    recent_k: Optional[jax.Array] = None,   # (B, Hkv, R, D)
    recent_v: Optional[jax.Array] = None,
    recent_len: Optional[jax.Array] = None,  # (B,)
    self_k: Optional[jax.Array] = None,      # (B, Hkv, 1, D)
    self_v: Optional[jax.Array] = None,
    k_syn_scale: Optional[jax.Array] = None,  # (B, Hkv, M) — quantized
    v_syn_scale: Optional[jax.Array] = None,  # synopsis (DESIGN.md §15);
    kv_k_scale: Optional[jax.Array] = None,   # (B, Hkv, M) — quantized
    kv_v_scale: Optional[jax.Array] = None,   # sorted KV
    *,
    i_max: int,
    cluster_size: int,
    sm_scale: float = 1.0,
    cap: Optional[float] = None,
    impl: str = "pallas",
):
  """End-to-end fused AccuracyTrader decode attention over a serve-step
  cache slice: O(M + i_max*C + R) with k_syn/v_syn read ONCE.  Returns
  the normalised output (B, H, D) f32.  All-None scales keep the
  bit-identical unquantized path."""
  B, H, _ = q.shape
  Hkv, M = k_syn.shape[1], k_syn.shape[2]
  syn_scales = (None if k_syn_scale is None
                else (k_syn_scale, v_syn_scale))
  kv_scales = None if kv_k_scale is None else (kv_k_scale, kv_v_scale)
  scores, p_syn = synopsis_stage1(q, k_syn, v_syn, counts,
                                  sm_scale=sm_scale, cap=cap, impl=impl,
                                  syn_scales=syn_scales)
  if i_max > 0:
    _, selected = jax.lax.top_k(scores, min(i_max, M))
    selected = selected.astype(jnp.int32)
  else:
    selected = jnp.full((B, Hkv, 1), -1, jnp.int32)
  self_kv = (self_k, self_v) if self_k is not None else None
  extras = build_extras(recent_k, recent_v, recent_len, self_kv)
  p_ref = refine_stage2(
      q, k, v, selected, k_syn, v_syn, counts, cluster_size=cluster_size,
      sm_scale=sm_scale, cap=cap, impl=impl, extras=extras,
      syn_scales=syn_scales, kv_scales=kv_scales)
  out, _, _ = merge_partials(p_syn, p_ref)
  return out


@functools.partial(
    jax.jit,
    static_argnames=("i_max", "sm_scale", "impl", "return_diag"))
def synopsis_attention_fused(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_syn: jax.Array,
    v_syn: jax.Array,
    counts: jax.Array,
    k_syn_scale: Optional[jax.Array] = None,   # quantized-arena scales
    v_syn_scale: Optional[jax.Array] = None,   # (DESIGN.md §15)
    kv_k_scale: Optional[jax.Array] = None,
    kv_v_scale: Optional[jax.Array] = None,
    *,
    i_max: int,
    sm_scale: float = 1.0,
    impl: str = "pallas",
    return_diag: bool = False,
):
  """Fused drop-in for :func:`synopsis_attention` (same contract): one
  synopsis pass + decremental refinement instead of score + masked decode
  + gather + merge."""
  M = k_syn.shape[2]
  syn_scales = (None if k_syn_scale is None
                else (k_syn_scale, v_syn_scale))
  kv_scales = None if kv_k_scale is None else (kv_k_scale, kv_v_scale)
  scores, p_syn = synopsis_stage1(q, k_syn, v_syn, counts,
                                  sm_scale=sm_scale, impl=impl,
                                  syn_scales=syn_scales)
  _, selected = jax.lax.top_k(scores, min(i_max, M))
  selected = selected.astype(jnp.int32)
  C = k.shape[2] // M
  p_ref = refine_stage2(q, k, v, selected, k_syn, v_syn, counts,
                        cluster_size=C, sm_scale=sm_scale, impl=impl,
                        syn_scales=syn_scales, kv_scales=kv_scales)
  out, m, l = merge_partials(p_syn, p_ref)
  if return_diag:
    return out, (scores, selected, m, l)
  return out


# ---------------------------------------------------------------------------
# Unfused composition (benchmark baseline + paper-algebra oracle).
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("i_max", "sm_scale", "impl", "return_diag"))
def synopsis_attention(
    q: jax.Array,        # (B, H, D)   one decode step's queries
    k: jax.Array,        # (B, Hkv, S, D) cluster-contiguous original keys
    v: jax.Array,        # (B, Hkv, S, D)
    k_syn: jax.Array,    # (B, Hkv, M, D) centroid keys
    v_syn: jax.Array,    # (B, Hkv, M, D) centroid values
    counts: jax.Array,   # (B, M)
    *,
    i_max: int,
    sm_scale: float = 1.0,
    impl: str = "pallas",
    return_diag: bool = False,
):
  """AccuracyTrader attention: O(M + i_max*C) instead of O(S).

  Unselected clusters contribute count-weighted centroid terms (stage 1);
  the top-``i_max`` clusters contribute their original tokens exactly
  (stage 2).  With ``i_max == M`` this equals exact attention.

  Unfused: the synopsis is read twice (scores, then masked decode) and
  the three partials merge separately — the baseline the fused pipeline
  is benchmarked against.
  """
  M = k_syn.shape[2]
  scores = _scores(q, k_syn, sm_scale, impl)            # (B, Hkv, M)
  _, selected = jax.lax.top_k(scores, i_max)
  selected = selected.astype(jnp.int32)

  sel_onehot = jnp.any(jax.nn.one_hot(selected, M, dtype=jnp.bool_), axis=2)
  syn_bias = jnp.where(
      sel_onehot, NEG_INF,
      jnp.log(jnp.maximum(counts, 1)).astype(jnp.float32)[:, None, :])

  part_syn = _decode(q, k_syn, v_syn, syn_bias, sm_scale, impl,
                     block_s=min(512, M))
  C = k.shape[2] // M
  part_ref = _gather(q, k, v, selected, C, sm_scale, impl)
  out, m, l = merge_partials(part_syn, part_ref)
  if return_diag:
    return out, (scores, selected, m, l)
  return out


@functools.partial(jax.jit, static_argnames=("sm_scale", "cap", "impl"))
def exact_decode_attention(q, k, v, bias=None, *, sm_scale: float = 1.0,
                           cap: Optional[float] = None,
                           impl: str = "pallas"):
  """Exact GQA decode (baseline); returns normalised output only."""
  out, _, _ = _decode(q, k, v, bias, sm_scale, impl, cap=cap)
  return out


def decode_partials(q, k, v, bias=None, *, sm_scale: float = 1.0,
                    cap: Optional[float] = None,
                    impl: str = "pallas") -> Tuple[jax.Array, ...]:
  """Exact decode returning (out, m, l) — for cross-shard (SP) merging."""
  return _decode(q, k, v, bias, sm_scale, impl, cap=cap)
