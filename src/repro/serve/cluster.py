"""Multi-component scatter-gather serving tier (DESIGN.md §9).

The paper's architecture — a frontend scatter-gathering over massively
parallel components, each answering instantly from its local synopsis and
then refining the corpus parts most related to the request — realised
over the kernel serve path:

  * the corpus KV of every resident request is partitioned across N
    *components* laid out on the device mesh
    (`repro.dist.topology.ComponentTopology`; a ``("component",)`` mesh
    when the host has enough devices, a stacked single-device execution
    of the same math otherwise);
  * stage 1 runs the fused synopsis scoring on **all** components in
    parallel — one ``shard_map``-ed ``ops.synopsis_stage1`` over each
    component's ``k_syn``/``v_syn``/``counts`` shard;
  * the *frontend aggregator* merges the per-component score partials
    with a global top-k and allocates the per-step refinement budget
    across components proportionally to their synopsis relevance mass
    (:func:`allocate_budget`) — the paper's accuracy-aware part
    selection, generalized from clusters-within-a-component to
    components-within-a-cluster-of-machines;
  * the gather is *deadline-driven*: per step, each component is marked
    FULL (stage 1 + refinement), STAGE1 (its refinement is predicted to
    miss the step deadline — the synopsis answer, which always returns
    instantly, stands in) or DROP (partial execution: the component's
    entire contribution is skipped), and the online-softmax result
    composer folds exactly the granted partials;
  * with a replication factor R >= 2 (``ClusterConfig.replicas``,
    `ComponentTopology.replica_owner`) the gather additionally *hedges*:
    a component the predictor flags as likely to miss the step deadline
    has its refinement reissued to the shard's replica, the earlier of
    the two completions counts, and only when BOTH are predicted to miss
    does the stage-1 answer (or DROP, under partial execution) stand in.

  All latency prediction and budget decisions go through the shared
  control plane (`repro.control`, DESIGN.md §10): a pluggable per-bucket
  latency predictor (EWMA by default, sliding-window quantile via
  ``ClusterConfig.predictor``) and one `DeadlineBudgetPolicy` that owns
  the FULL/STAGE1/DROP decision and the mass-proportional
  `allocate_budget` with stranded-budget recirculation.

`ClusterStepBackend` plugs the tier into `ServingEngine` as a drop-in
step backend: admission scatters each slot's built synopsis across the
components (per-slot routing, optionally rotated for balance), decode
steps run one compiled program per budget bucket, and the backend keeps a
measured-latency attribution per component (`ClusterMeasuredExport`)
that round-trips into the discrete-event simulator
(``ScatterGatherService(step_backend=...)`` /
``ComponentModel.submit(service_ms=<per-component vector>)``).

CPU-proxy caveat (EXPERIMENTS.md §Cluster): on a single host the N
components execute as one program, so the *total* step wall time is
measured and attributed to components in proportion to their corpus
share and allocated budget (``l_c = base·share_c + slope·b_c``); the
per-step interference noise and straggler draws model the co-located
jobs the measurement cannot see, exactly as `serving.latency
.ComponentModel` does for the simulator.  The engine clock then advances
by the *parallel* completion time (max over gathered components), which
is what the frontend of a real N-machine deployment would observe.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.control import (MODE_DROP, MODE_FULL, MODE_STAGE1, RetryPolicy,
                           allocate_budget, make_predictor,
                           realized_recovery)
from repro.control.estimator import coverage_profile
from repro.dist import sharding as shd
from repro.dist.topology import ComponentTopology, make_component_mesh
from repro.kernels import ops
from repro.serve import kv_cache as kvc
from repro.serve.resilience import FaultPlan, FaultSpec
from repro.serve.serve_step import make_serve_step

NEG_INF = ops.NEG_INF

__all__ = ["MODE_DROP", "MODE_STAGE1", "MODE_FULL", "allocate_budget",
           "ClusterConfig", "ClusterStepBackend", "ClusterMeasuredExport",
           "make_cluster_attention", "gain_rank", "gain_budgets"]


@dataclasses.dataclass
class ClusterConfig:
  """Scatter-gather tier knobs (model shape comes from the ModelConfig)."""
  n_components: int = 4
  skew: float = 0.0            # Zipf exponent over component corpus shares
  alloc: str = "mass"          # "mass" (∝ relevance mass) | "topk" (global
                               # by raw score) | "gain" (global by marginal
                               # accuracy gain: count-biased score,
                               # DESIGN.md §13)
  route: str = "fixed"         # per-slot cluster routing; "rotate" balances
  replicas: int = 1            # shard copies; R >= 2 enables hedged reissue
  predictor: str = "ewma"      # control-plane wall predictor ("quantile:90"
                               # makes hedging target a tail percentile)
  recirculate: bool = True     # stranded-budget recirculation in allocate
  interference: float = 0.25   # lognormal sigma (co-located jobs, per step)
  straggler_prob: float = 0.02
  straggler_scale: float = 8.0
  use_mesh: Optional[bool] = None   # None -> mesh; stacked only on a
                                    # CPU host short of devices
  seed: int = 0
  # -- resilience (DESIGN.md §11; all off by default: faults=None and
  # retries=1 take the exact legacy plan/account path, bit-identical) ----
  faults: Optional[FaultSpec] = None   # injected fault world (resilience.py)
  recovery: bool = True        # False: no retry / no stage-1 fallback —
                               # a dead shard stalls the gather and its
                               # mass is dropped (the chaos baseline)
  retries: int = 1             # bounded reissues per shard per step over
                               # the replica ring (1 = legacy one-shot
                               # hedge; needs replicas >= 2)
  retry_backoff: float = 0.5   # retry r waits timeout*backoff*mult^(r-1)
  retry_backoff_mult: float = 2.0
  fault_stall_wait: float = 3.0   # no-recovery: gather waits this many
                                  # step deadlines on a dead shard


# ---------------------------------------------------------------------------
# Frontend aggregator: global ranking + budget allocation across components
# (the allocation itself — mass-proportional with stranded-budget
# recirculation — lives in the control plane: repro.control.allocate_budget).
# ---------------------------------------------------------------------------

def _frontend_rank(sc_all: jax.Array, i_max: int):
  """Global ranking over the gathered per-component scores.

  sc_all (B, Hkv, N, Mp) with padded slots at NEG_INF.  Returns
  (gsel (B, Hkv, K) flat cluster ids with -1 pads — or None at budget 0 —
  and the per-component relevance mass (B, Hkv, N))."""
  B, Hkv, N, Mp = sc_all.shape
  flat = sc_all.reshape(B, Hkv, N * Mp)
  gmax = jnp.max(flat, axis=-1)                               # (B, Hkv)
  mass = jnp.sum(jnp.exp(sc_all - gmax[:, :, None, None]), axis=-1)
  if i_max <= 0:
    return None, mass
  K = min(i_max, N * Mp)
  tsc, gsel = jax.lax.top_k(flat, K)
  gsel = jnp.where(tsc > NEG_INF / 2, gsel.astype(jnp.int32), -1)
  return gsel, mass


def gain_rank(sc_all: jax.Array, counts: jax.Array, i_max: int):
  """Marginal-accuracy-gain global ranking (DESIGN.md §13).

  Refining cluster m removes its synopsis approximation error, and the
  share of the answer it owns — hence the loss the refinement recovers —
  is its stage-1 probability mass ``exp(score_m) · count_m``.  Greedy
  top-k on ``score + log(count)`` is therefore the budget split that
  maximizes the predicted covered mass per cluster refined, vs "mass"
  allocation which spreads budget ∝ per-*component* totals even when one
  component's clusters individually dominate.  ``sc_all`` (B, Hkv, N,
  Mp) padded scores, ``counts`` (B, N, Mp).  Returns flat global ids
  (B, Hkv, K) with -1 pads — a drop-in for `_frontend_rank`'s gsel."""
  B, Hkv, N, Mp = sc_all.shape
  bias = jnp.log(jnp.maximum(counts, 1e-30))[:, None, :, :]
  g = jnp.where(sc_all > NEG_INF / 2, sc_all + bias, NEG_INF)
  flat = g.reshape(B, Hkv, N * Mp)
  K = min(i_max, N * Mp)
  tsc, gsel = jax.lax.top_k(flat, K)
  return jnp.where(tsc > NEG_INF / 2, gsel.astype(jnp.int32), -1)


def gain_budgets(gsel: jax.Array, Mp: int, N: int) -> jax.Array:
  """Per-component budget vector implied by a global selection: how many
  of the selected flat ids land on each component.  Conserves the spend
  by construction — ``sum == number of non-pad selections`` — which the
  conservation tests check against `allocate_budget`'s invariant."""
  comp_of = jnp.where(gsel >= 0, gsel // Mp, -1)
  onehot = comp_of[..., None] == jnp.arange(N)[None, None, None, :]
  return jnp.sum(onehot.astype(jnp.int32), axis=2)          # (B, Hkv, N)


def _select_local(c, sc_local, gsel, budgets, alloc, i_max, Mp):
  """Per-component stage-2 selection (local cluster ids, -1 pads).

  ``alloc="topk"`` / ``alloc="gain"``: the component refines exactly the
  globally top-ranked clusters it owns (two-level top-k — "topk" equals
  the single-component reference; "gain" ranks by count-biased score,
  see :func:`gain_rank`).  ``alloc="mass"``: the component refines its
  own top-scored clusters up to the budget the frontend allocated it."""
  if alloc in ("topk", "gain"):
    comp_of = jnp.where(gsel >= 0, gsel // Mp, -1)
    return jnp.where(comp_of == c, gsel % Mp, -1).astype(jnp.int32)
  Kc = min(i_max, Mp)
  tsc, sel = jax.lax.top_k(sc_local, Kc)
  b_c = jnp.take(budgets, c, axis=-1)[..., None]              # (B, Hkv, 1)
  keep = (jnp.arange(Kc)[None, None, :] < b_c) & (tsc > NEG_INF / 2)
  return jnp.where(keep, sel.astype(jnp.int32), -1)


def _pick_mode(mode, full, syn):
  """Deadline-driven partial gather: FULL -> merged stage-1+2 partial,
  STAGE1 -> the synopsis answer alone, DROP -> a zero-weight partial."""
  drop = (jnp.zeros_like(full[0]), jnp.full_like(full[1], NEG_INF),
          jnp.zeros_like(full[2]))
  return tuple(
      jnp.where(mode == MODE_FULL, f,
                jnp.where(mode == MODE_STAGE1, s, d))
      for f, s, d in zip(full, syn, drop))


def _extras_partial(q, csl, self_kv, *, sm_scale, cap, impl):
  """Frontend-owned recent-ring + self-KV partial, merged exactly once at
  the composer (never routed to a component, so partial gather can never
  lose the new token)."""
  extras = ops.build_extras(csl.get("recent_k"), csl.get("recent_v"),
                            csl.get("recent_len"), self_kv)
  if extras is None:
    return None
  ek, ev, eb = extras
  bias = jnp.broadcast_to(eb[:, None, :],
                          (eb.shape[0], ek.shape[1], eb.shape[1]))
  return ops.decode_partials(q, ek, ev, bias, sm_scale=sm_scale, cap=cap,
                             impl=impl)


# ---------------------------------------------------------------------------
# The scatter-gather attention body (stacked + shard_map executions of the
# same math).  Plugged into make_serve_step(attention_fn=...).
# ---------------------------------------------------------------------------

def make_cluster_attention(topo: ComponentTopology, alloc: str = "mass",
                           mesh=None, recirculate: bool = True,
                           mode_caps: bool = False,
                           telemetry: bool = False):
  """Returns ``attention_fn(q, cache_sl, ...) -> (ctx, aux)`` over the
  component-partitioned cache layout (DESIGN.md §9):

    k/v          (B, Hkv, N, m_max*C, D)   per-component corpus shards
    k_syn/v_syn  (B, Hkv, N, m_max, D)     per-component centroid tables
    counts       (B, N, m_max)             0 on padded slots
    fe_mode      (N,) int32                per-component gather mode

  ``aux`` carries per-layer telemetry: ``fe_cover`` (N,) mean refined
  clusters per component and ``fe_mass`` (N,) mean relevance-mass share;
  with ``telemetry=True`` (the ε-or-deadline contracts, DESIGN.md §13)
  also ``est_profile`` (B, N*Mp+1) — the stage-1 coverage profile over
  the GLOBAL cluster ranking, the online loss estimator's raw signal.
  Off by default so contract="deadline" step programs stay bit-identical.

  ``mode_caps`` (resilience, DESIGN.md §11): a component gathered as
  STAGE1/DROP never folds its refinement, so budget allocated to it is
  wasted — with mode-aware caps its allocation cap is zeroed and
  `allocate_budget`'s recirculation respends that budget on the live FULL
  components instead.  Off by default: it changes the default path's
  allocation, so only the resilient backend enables it.
  """
  N, Mp = topo.n_components, topo.m_max

  def attention(q, csl, *, i_max, cluster_size, sm_scale, cap=None,
                self_kv=None, impl="xla"):
    if mesh is not None:
      return _cluster_sharded(
          q, csl, topo, alloc, mesh, i_max=i_max,
          cluster_size=cluster_size, sm_scale=sm_scale, cap=cap,
          self_kv=self_kv, impl=impl, recirculate=recirculate,
          mode_caps=mode_caps, telemetry=telemetry)
    return _cluster_stacked(
        q, csl, topo, alloc, i_max=i_max, cluster_size=cluster_size,
        sm_scale=sm_scale, cap=cap, self_kv=self_kv, impl=impl,
        recirculate=recirculate, mode_caps=mode_caps, telemetry=telemetry)

  return attention


def _cluster_stacked(q, csl, topo, alloc, *, i_max, cluster_size, sm_scale,
                     cap, self_kv, impl, recirculate=True, mode_caps=False,
                     telemetry=False):
  """Single-device execution: the N components run as an unrolled loop
  over the component axis — identical math to the shard_map body."""
  k, v = csl["k"], csl["v"]
  k_syn, v_syn, counts = csl["k_syn"], csl["v_syn"], csl["counts"]
  fe_mode = csl["fe_mode"]
  N, Mp = k_syn.shape[2], k_syn.shape[3]

  def _slice_scales(names, c):
    # Quantized-arena dequant scales (§15) per component, when present.
    if names[0] not in csl:
      return None
    return tuple(csl[n][:, :, c] for n in names)

  scs, psyns = [], []
  for c in range(N):
    sc_c, p_c = ops.synopsis_stage1(
        q, k_syn[:, :, c], v_syn[:, :, c], counts[:, c],
        sm_scale=sm_scale, cap=cap, impl=impl, valid=counts[:, c] > 0,
        syn_scales=_slice_scales(("k_syn_scale", "v_syn_scale"), c))
    scs.append(sc_c)
    psyns.append(p_c)
  sc_all = jnp.stack(scs, axis=2)                         # (B, Hkv, N, Mp)
  gsel, mass = _frontend_rank(sc_all, i_max)
  if gsel is not None and alloc == "gain":
    gsel = gain_rank(sc_all, counts, i_max)
  budgets = None
  if gsel is not None and alloc == "mass":
    caps = jnp.sum(sc_all > NEG_INF / 2, axis=-1)         # (B, Hkv, N)
    if mode_caps:
      caps = jnp.where(fe_mode[None, None, :] == MODE_FULL, caps, 0)
    budgets = allocate_budget(mass, i_max, caps, recirculate=recirculate)

  acc = None
  cover = []
  for c in range(N):
    if gsel is None:
      p_full = psyns[c]
      cover.append(jnp.float32(0.0))
    else:
      sel = _select_local(c, scs[c], gsel, budgets, alloc, i_max, Mp)
      p_ref = ops.refine_stage2(
          q, k[:, :, c], v[:, :, c], sel, k_syn[:, :, c], v_syn[:, :, c],
          counts[:, c], cluster_size=cluster_size, sm_scale=sm_scale,
          cap=cap, impl=impl,
          syn_scales=_slice_scales(("k_syn_scale", "v_syn_scale"), c),
          kv_scales=_slice_scales(("k_scale", "v_scale"), c))
      p_full = ops.merge_partials(psyns[c], p_ref)
      cover.append(jnp.mean(jnp.sum((sel >= 0).astype(jnp.float32), -1)))
    contrib = _pick_mode(fe_mode[c], p_full, psyns[c])
    acc = contrib if acc is None else ops.merge_partials(acc, contrib)

  p_ex = _extras_partial(q, csl, self_kv, sm_scale=sm_scale, cap=cap,
                         impl=impl)
  if p_ex is not None:
    acc = ops.merge_partials(acc, p_ex)
  mass_frac = mass / jnp.maximum(jnp.sum(mass, -1, keepdims=True), 1e-30)
  aux = {"fe_cover": jnp.stack(cover),
         "fe_mass": jnp.mean(mass_frac, axis=(0, 1))}
  if telemetry:
    B = sc_all.shape[0]
    aux["est_profile"] = coverage_profile(
        sc_all.reshape(B, sc_all.shape[1], N * Mp),
        counts.reshape(B, N * Mp),
        rank="mass" if alloc == "gain" else "score")
  return acc[0], aux


def _cluster_sharded(q, csl, topo, alloc, mesh, *, i_max, cluster_size,
                     sm_scale, cap, self_kv, impl, recirculate=True,
                     mode_caps=False, telemetry=False):
  """shard_map execution over the ``("component",)`` mesh: every device is
  one component; the score all-gather + replicated frontend logic is the
  aggregator, the partials all-gather + fold is the result composer."""
  from jax.sharding import PartitionSpec as P  # noqa: PLC0415
  N, Mp = topo.n_components, topo.m_max
  corpus = P(None, None, "component", None, None)
  specs = {"k": corpus, "v": corpus, "k_syn": corpus, "v_syn": corpus,
           "counts": P(None, "component", None),
           "fe_mode": P("component")}
  for name in ("k_syn_scale", "v_syn_scale", "k_scale", "v_scale"):
    if name in csl:          # quantized arena (§15)
      specs[name] = P(None, None, "component", None)
  for name in ("recent_k", "recent_v"):
    if name in csl:
      specs[name] = P(None, None, None, None)
  if "recent_len" in csl:
    specs["recent_len"] = P(None)
  csl = {kk: csl[kk] for kk in specs}
  q_spec = P(None, None, None)
  self_spec = (P(None, None, None, None),) * 2 if self_kv is not None \
      else P()

  def body(q, cache, self_kv):
    with shd.manual_axes({"component"}):
      sid = jax.lax.axis_index("component")
      k_l, v_l = cache["k"][:, :, 0], cache["v"][:, :, 0]
      ks_l, vs_l = cache["k_syn"][:, :, 0], cache["v_syn"][:, :, 0]
      counts_l = cache["counts"][:, 0]
      mode_l = cache["fe_mode"][0]
      syn_scales = (None if "k_syn_scale" not in cache else
                    (cache["k_syn_scale"][:, :, 0],
                     cache["v_syn_scale"][:, :, 0]))
      kv_scales = (None if "k_scale" not in cache else
                   (cache["k_scale"][:, :, 0], cache["v_scale"][:, :, 0]))

      sc_l, p_syn = ops.synopsis_stage1(
          q, ks_l, vs_l, counts_l, sm_scale=sm_scale, cap=cap, impl=impl,
          valid=counts_l > 0, syn_scales=syn_scales)
      sc = jax.lax.all_gather(sc_l, "component", axis=2, tiled=True)
      B, Hkv = sc.shape[:2]
      sc_all = sc.reshape(B, Hkv, N, Mp)
      gsel, mass = _frontend_rank(sc_all, i_max)
      counts_g = None
      if alloc == "gain" or telemetry:
        # One extra small (B, Mp) all-gather: the global counts the
        # count-biased gain ranking and the coverage profile both need.
        counts_g = jax.lax.all_gather(cache["counts"][:, 0], "component",
                                      axis=1, tiled=True)    # (B, N*Mp)
      if gsel is not None and alloc == "gain":
        gsel = gain_rank(sc_all, counts_g.reshape(B, N, Mp), i_max)

      if gsel is None:
        p_full = p_syn
        cover_l = jnp.zeros((1,), jnp.float32)
      else:
        budgets = None
        if alloc == "mass":
          caps = jnp.sum(sc_all > NEG_INF / 2, axis=-1)    # (B, Hkv, N)
          if mode_caps:
            modes = jax.lax.all_gather(cache["fe_mode"], "component",
                                       tiled=True)          # (N,)
            caps = jnp.where(modes[None, None, :] == MODE_FULL, caps, 0)
          budgets = allocate_budget(mass, i_max, caps,
                                    recirculate=recirculate)
        sel = _select_local(sid, sc_l, gsel, budgets, alloc, i_max, Mp)
        p_ref = ops.refine_stage2(
            q, k_l, v_l, sel, ks_l, vs_l, counts_l,
            cluster_size=cluster_size, sm_scale=sm_scale, cap=cap,
            impl=impl, syn_scales=syn_scales, kv_scales=kv_scales)
        p_full = ops.merge_partials(p_syn, p_ref)
        cover_l = jnp.mean(
            jnp.sum((sel >= 0).astype(jnp.float32), -1))[None]
      contrib = _pick_mode(mode_l, p_full, p_syn)

      gathered = [jax.lax.all_gather(x[None], "component", axis=0,
                                     tiled=True) for x in contrib]
      og, mg, lg = gathered
      acc = (og[0], mg[0], lg[0])
      for i in range(1, N):
        acc = ops.merge_partials(acc, (og[i], mg[i], lg[i]))
      p_ex = _extras_partial(q, cache, self_kv, sm_scale=sm_scale,
                             cap=cap, impl=impl)
      if p_ex is not None:
        acc = ops.merge_partials(acc, p_ex)
      cover = jax.lax.all_gather(cover_l, "component", axis=0, tiled=True)
      mass_frac = mass / jnp.maximum(jnp.sum(mass, -1, keepdims=True),
                                     1e-30)
      outs = (acc[0], cover, jnp.mean(mass_frac, axis=(0, 1)))
      if telemetry:
        outs = outs + (coverage_profile(
            sc_all.reshape(B, Hkv, N * Mp), counts_g,
            rank="mass" if alloc == "gain" else "score"),)
      return outs

  n_out = 4 if telemetry else 3
  res = jax.shard_map(
      body, mesh=mesh, in_specs=(q_spec, specs, self_spec),
      out_specs=(P(),) * n_out, axis_names=frozenset({"component"}),
      check_vma=False)(q, csl, self_kv)
  aux = {"fe_cover": res[1], "fe_mass": res[2]}
  if telemetry:
    aux["est_profile"] = res[3]
  return res[0], aux


# ---------------------------------------------------------------------------
# ServingEngine step backend: per-slot routing, plan/account around each
# dispatched step, measured-latency attribution per component.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _StepPlan:
  """One step's pre-dispatch gather decision + this step's noise draws
  (the same draws price the realized completion once the wall time is
  measured, so decision and accounting see one consistent world).

  The resilience fields (default None = legacy path, DESIGN.md §11)
  carry this step's fault world and the recovery ladder's decisions so
  ``account`` realizes exactly the retries ``plan_step`` dispatched."""
  fe_mode: jax.Array           # (N,) int32 device array fed into the step
  mode: np.ndarray             # same, host-side
  noise: np.ndarray            # per-component interference multipliers
  noise2: np.ndarray           # independent draws for the replica reissues
  hedged: np.ndarray           # (N,) bool: shard c's refinement reissued
  b_est: np.ndarray            # frontend's expected per-component budget
  deadline_ms: float
  retries: Optional[np.ndarray] = None   # (N,) reissues dispatched
  noise_r: Optional[np.ndarray] = None   # (K, N) per-retry draws
  delays: Optional[np.ndarray] = None    # (K, N) backoff dispatch offsets
  alive: Optional[np.ndarray] = None     # (N,) fault world: primary alive
  slow: Optional[np.ndarray] = None      # (N,) fault slowdown multipliers


class ClusterStepBackend:
  """Drop-in `ServingEngine` step backend running the scatter-gather tier.

  The engine calls ``plan_step`` (frontend gather decision from the
  calibrated per-component latency attribution + this step's interference
  draws), dispatches the returned program, and calls ``account`` with the
  measured wall time — which recalibrates the attribution, computes the
  per-request accuracy contribution and the *parallel* completion time
  the engine clock advances by (see module docstring, CPU-proxy note)."""

  def __init__(self, ccfg: ClusterConfig):
    self.ccfg = ccfg
    self.engine = None

  # -- binding ---------------------------------------------------------------
  def bind(self, engine) -> None:
    """Called by ServingEngine.__init__ once shapes are known."""
    cc = self.ccfg
    self.engine = engine
    self.cfg = engine.cfg
    self.impl = engine.impl
    self.M = engine.M
    self.n_slots = engine.ecfg.n_slots
    self.prompt_len = engine.ecfg.prompt_len
    self.accuracy_fn = engine.accuracy_fn
    if cc.alloc not in ("mass", "topk", "gain"):
      raise ValueError(
          f"alloc {cc.alloc!r} not in ('mass', 'topk', 'gain')")
    if cc.route not in ("fixed", "rotate"):
      raise ValueError(f"route {cc.route!r} not in ('fixed', 'rotate')")
    self.topo = ComponentTopology.plan(self.M, cc.n_components,
                                       skew=cc.skew, replicas=cc.replicas)
    self.mesh = make_component_mesh(cc.n_components, cc.use_mesh)
    # Resilience (DESIGN.md §11): the fault world, the bounded-retry
    # policy over the replica ring, and mode-aware allocation caps.  The
    # default config (faults=None, recovery=True, retries=1) keeps
    # ``resilient`` False and every fault/recovery branch below is
    # skipped — the legacy plan/account path runs bit-identically.
    if cc.retries < 0:
      raise ValueError(f"retries {cc.retries} < 0")
    self.faults = FaultPlan(cc.faults, cc.n_components)
    self.resilient = self.faults.enabled or cc.retries != 1 \
        or not cc.recovery
    self.retry_policy = RetryPolicy(max_retries=cc.retries,
                                    backoff_base=cc.retry_backoff,
                                    backoff_mult=cc.retry_backoff_mult)
    self.n_retries = cc.retries if cc.replicas > 1 and cc.recovery else 0
    if self.n_retries:
      # Retry r's holder: walk the shard's replica ring (retries beyond
      # the materialized copies re-ask earlier holders after backoff).
      self.retry_of = np.asarray(
          [[self.topo.replica_owner(c, 1 + r % (cc.replicas - 1))
            for c in range(cc.n_components)]
           for r in range(self.n_retries)])
    else:
      self.retry_of = None
    self.step_idx = 0
    self.fault_stats = {"crash_steps": 0, "retries": 0,
                        "stage1_fallbacks": 0, "dropped": 0}
    # ε-or-deadline contracts (DESIGN.md §13): the coverage-profile
    # telemetry the online estimator reads.  Gated on the engine's
    # contract so contract="deadline" step programs stay bit-identical.
    self.telemetry = engine.ecfg.contract != "deadline"
    self.attention = make_cluster_attention(self.topo, alloc=cc.alloc,
                                            mesh=self.mesh,
                                            recirculate=cc.recirculate,
                                            mode_caps=self.resilient,
                                            telemetry=self.telemetry)
    # Per-component corpus share: the latency/accuracy attribution
    # weights.  Rotation mixes ownership across slots via shifts
    # 0..n_slots-1, so the attribution is the mean of exactly those
    # rotations of the plan — uniform only once n_slots covers the
    # component ring (fewer slots leave a skewed corpus genuinely
    # concentrated on the first components, and the attribution must
    # say so or plan_step underpredicts the hot components).
    if cc.route == "rotate":
      self.comp_share = np.mean(
          [np.roll(self.topo.shares, s) for s in range(self.n_slots)],
          axis=0)
    else:
      self.comp_share = np.asarray(self.topo.shares)
    # Control plane: one pluggable wall-time predictor per backend (the
    # attribution base — pre-dispatch predictions AND the hedging
    # decision read it).  Gather-mode decisions go through the engine's
    # DeadlineBudgetPolicy (`engine.controller.gather_modes`): one
    # policy object per engine owns budgets AND modes.
    self.predictor = make_predictor(cc.predictor)
    # Primary -> first-replica holder, per shard (ring placement).
    self.replica_of = np.asarray(
        [self.topo.replica_owner(c, 1) for c in range(cc.n_components)]) \
        if cc.replicas > 1 else None
    self.mass_ewma = self.comp_share.copy()
    self.reseed(cc.seed)
    self._write = self._make_write()

  def reseed(self, seed: int) -> None:
    """Re-seed the interference/straggler draw stream.  Called per
    measurement window (`run_open_loop`) so a window's draw sequence is a
    pure function of (config seed, window seed) — warmup and prior
    windows cannot shift it, and BENCH_cluster.json regenerates with the
    same noise world every time."""
    self.rng = np.random.default_rng(
        np.random.SeedSequence([int(self.ccfg.seed),
                                int(seed) & 0x7fffffff]))
    # The injected fault world and the step counter rewind with the draw
    # stream: a window's faults are a pure function of (spec seed,
    # window seed, step index), independent of warmup history.
    self.step_idx = 0
    if getattr(self, "faults", None) is not None:
      self.faults.reseed(seed)

  # -- cache layout ----------------------------------------------------------
  def zeros_cache(self) -> Dict[str, jax.Array]:
    """The engine slot pool with corpus leaves in component layout."""
    base = kvc.zeros_cache(self.cfg, self.n_slots, self.prompt_len,
                           synopsis=True)
    nb, na, B, Hkv, S, D = base["k"].shape
    C = self.cfg.synopsis.cluster_size
    N, Mp = self.topo.n_components, self.topo.m_max
    base["k"] = jnp.zeros((nb, na, B, Hkv, N, Mp * C, D),
                          base["k"].dtype)
    base["v"] = jnp.zeros_like(base["k"])
    base["k_syn"] = jnp.zeros((nb, na, B, Hkv, N, Mp, D),
                              base["k_syn"].dtype)
    base["v_syn"] = jnp.zeros_like(base["k_syn"])
    base["counts"] = jnp.zeros((nb, na, B, N, Mp), jnp.float32)
    for name in ("k_syn_scale", "v_syn_scale", "k_scale", "v_scale"):
      if name in base:       # quantized arena (§15): component layout too
        base[name] = jnp.zeros((nb, na, B, Hkv, N, Mp), jnp.float32)
    return base

  def _scatter(self, syn: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Route one request's built synopsis cache (B=1, cluster-contiguous)
    into per-component shards padded to m_max (counts 0 on pads)."""
    C = self.cfg.synopsis.cluster_size
    topo = self.topo
    Mp = topo.m_max

    def split(x, axis, unit):
      parts = []
      for c in range(topo.n_components):
        off, cnt = topo.offsets[c] * unit, topo.counts[c] * unit
        sl = jax.lax.slice_in_dim(x, off, off + cnt, axis=axis)
        pad = Mp * unit - cnt
        if pad:
          widths = [(0, 0)] * x.ndim
          widths[axis] = (0, pad)
          sl = jnp.pad(sl, widths)
        parts.append(sl)
      return jnp.stack(parts, axis=axis)

    # The shared-immutable half (kvc.ARENA_LEAVES) is what scatters —
    # private leaves (recent ring, pos, SSM state) pass through slot-
    # local.  A corpus-cache arena is pre-scatter canonical state, so a
    # shared arena scatters bit-identically to a privately built one
    # (tests/test_cluster.py).
    out = dict(syn)
    for name in kvc.ARENA_LEAVES:
      if name not in syn:    # scale leaves exist only under quantization
        continue
      if name == "counts":
        out[name] = split(syn[name], axis=3, unit=1)
      else:
        out[name] = split(syn[name], axis=4,
                          unit=C if name in ("k", "v") else 1)
    return out

  def _make_write(self):
    bx = kvc.slot_batch_axes(self.cfg, self.n_slots, self.prompt_len,
                             synopsis=True)
    rotate = self.ccfg.route == "rotate"

    def write(cache, syn, slot):
      sub = self._scatter(syn)
      if rotate:
        # Per-slot routing: slot s's cluster range r lands on component
        # (r + s) % N, spreading skewed ranges across components.
        for name in kvc.ARENA_LEAVES:
          if name not in sub:
            continue
          sub[name] = jnp.roll(sub[name], slot,
                               axis=3 if name == "counts" else 4)
      return kvc.write_slot(cache, sub, slot, bx)

    return jax.jit(write)

  def write_slot(self, cache, syn, slot):
    return self._write(cache, syn, slot)

  # -- the compiled step -----------------------------------------------------
  def step_fn(self, budget: int):
    """One jitted program per budget bucket; ``fe_mode`` is a traced
    input, so gather decisions never recompile."""
    step = make_serve_step(self.cfg, mode="synopsis", i_max=budget,
                           impl=self.impl, attention_fn=self.attention)

    @jax.jit
    def run(params, cache, tok, fe_mode):
      cache = dict(cache)
      cache["fe_mode"] = fe_mode
      return step(params, cache, tok)

    return run

  def full_mode(self) -> jax.Array:
    return jnp.full((self.topo.n_components,), MODE_FULL, jnp.int32)

  # -- frontend plan / account ----------------------------------------------
  def _units(self, b_vec: np.ndarray) -> np.ndarray:
    """Rows-read compute attribution per component: stage 1 streams the
    component's ``share_c * M`` centroids, refinement streams ``b_c``
    clusters of C original tokens each."""
    C = self.cfg.synopsis.cluster_size
    return self.comp_share * self.M + np.maximum(b_vec, 0.0) * C

  def _draw_noise(self) -> np.ndarray:
    """One (N,) interference + straggler multiplier draw.  Two draws per
    step (primary + replica path) are consumed regardless of the
    replication factor, so R=1 and R=2 runs with the same seeds see the
    same primary noise world."""
    cc = self.ccfg
    N = self.topo.n_components
    noise = self.rng.lognormal(0.0, cc.interference, N)
    return np.where(self.rng.random(N) < cc.straggler_prob,
                    noise * cc.straggler_scale, noise)

  def _hedge_time(self, wall: float, u: np.ndarray, usum: float,
                  noise: np.ndarray, noise2: np.ndarray) -> np.ndarray:
    """Completion of shard c's reissue on its replica j = replica_of[c]:
    the replica first finishes its own shard — u[j] at noise[j], the
    SAME draw that prices j's own completion this step, so a reissue can
    never finish before the machine it queues behind is free — then
    streams c's stage-1 + granted clusters again (u[c]) under the
    reissue's independent draw noise2[j].  ONE expression shared by the
    hedging decision (plan_step) and the realized accounting (account),
    so they can never drift apart."""
    j = self.replica_of
    return wall * (u[j] * noise[j] + u * noise2[j]) / usum

  def _retry_times(self, wall: float, u: np.ndarray, usum: float,
                   noise: np.ndarray, noise_r: np.ndarray,
                   slow: np.ndarray, delays: np.ndarray) -> np.ndarray:
    """Completion of shard c's retry r on holder jr = retry_of[r, c]:
    dispatched after the backoff delay, the holder first finishes its
    own shard — u[jr] at its fault slowdown and the SAME noise draw that
    prices jr's own completion this step — then streams c's stage-1 +
    granted clusters again under the retry's independent draw.  The
    K=1 / delay-0 / no-fault row is exactly ``_hedge_time``.  ONE
    expression shared by plan_step and account (DESIGN.md §11)."""
    jr = self.retry_of                                        # (K, N)
    nr = np.take_along_axis(noise_r, jr, axis=1)              # (K, N)
    return delays + wall * (u[jr] * slow[jr] * noise[jr]
                            + u[None, :] * slow[jr] * nr) / usum

  def plan_step(self, budget: int, step_deadline_ms: float) -> _StepPlan:
    """Pre-dispatch gather decision: predict each component's completion
    (control-plane wall predictor for this bucket, attributed by rows
    read, times this step's interference / straggler draws), hedge the
    predicted stragglers onto their shard replicas (R >= 2: the reissue
    queues behind the replica's own work and the earlier completion
    counts), and let the policy mark the components that still cannot
    make the step deadline STAGE1 (accuracytrader: the synopsis answer
    stands in) or DROP (partial execution: the result is skipped).

    With resilience on (injected faults and/or retries != 1) the single
    hedge generalizes to the control plane's recovery ladder
    (``recover_modes``, DESIGN.md §11): dead primaries and predicted
    stragglers retry on the replica ring with exponential backoff, and a
    shard with no live path inside the deadline terminally degrades to
    its stage-1 synopsis (accuracytrader) or is dropped (partial)."""
    massf = self.mass_ewma / max(self.mass_ewma.sum(), 1e-30)
    b_est = float(budget) * massf
    u = self._units(b_est)
    usum = max(u.sum(), 1e-30)
    noise, noise2 = self._draw_noise(), self._draw_noise()
    wall = self.predictor.predict(budget)
    if not self.resilient:
      t_pred = wall * (u / usum) * noise
      t_hedged = None
      if self.replica_of is not None:
        t_hedged = self._hedge_time(wall, u, usum, noise, noise2)
      mode, hedged = self.engine.controller.gather_modes(
          t_pred, step_deadline_ms, t_hedged)
      return _StepPlan(fe_mode=jnp.asarray(mode), mode=mode, noise=noise,
                       noise2=noise2, hedged=hedged, b_est=b_est,
                       deadline_ms=step_deadline_ms)
    fstate = self.faults.at(self.step_idx)
    alive, slow = fstate.alive, fstate.slow
    t_base = wall * (u / usum)           # per-component predictor timeout
    t_pred = t_base * noise * slow
    k = self.n_retries
    t_retry = retry_alive = delays = noise_r = None
    if k:
      noise_r = np.stack([noise2] + [self._draw_noise()
                                     for _ in range(k - 1)])
      delays = self.retry_policy.delays(t_base)               # (K, N)
      t_retry = self._retry_times(wall, u, usum, noise, noise_r, slow,
                                  delays)
      retry_alive = alive[self.retry_of]
    mode, retries, _ = self.engine.controller.recover_modes(
        t_pred, step_deadline_ms, t_retry=t_retry, alive=alive,
        retry_alive=retry_alive)
    if not self.ccfg.recovery:
      # Chaos baseline: no retries and no synopsis fallback — a dead
      # shard's mass simply drops (its stall is priced in account).
      mode = np.where(alive, mode, MODE_DROP).astype(np.int32)
      retries = np.zeros_like(retries)
    return _StepPlan(fe_mode=jnp.asarray(mode), mode=mode, noise=noise,
                     noise2=noise2, hedged=retries > 0, b_est=b_est,
                     deadline_ms=step_deadline_ms, retries=retries,
                     noise_r=noise_r, delays=delays, alive=alive,
                     slow=slow)

  def account(self, budget: int, wall_ms: float, plan: _StepPlan, st,
              warming: bool = False) -> Dict[str, float]:
    """Post-step accounting: fold the measured wall into the control-plane
    predictor, attribute it to components by the *actually refined* rows,
    take the hedged min for reissued shards (the same draws that made the
    hedging decision price the realized completions), and return the
    parallel completion time (max over the gathered components' effective
    times — what the frontend of a real N-machine deployment would wait
    for) plus the step's accuracy contribution."""
    full = plan.mode == MODE_FULL
    if not warming:
      self.predictor.observe(budget, wall_ms)
      if "fe_mass" in st:
        m = np.asarray(st["fe_mass"]).mean(axis=(0, 1))
        mix = 0.7 * self.mass_ewma + 0.3 * m
        self.mass_ewma = mix / max(mix.sum(), 1e-30)
    cover = np.asarray(st["fe_cover"]).mean(axis=(0, 1)) \
        if "fe_cover" in st else np.zeros_like(self.comp_share)
    u = self._units(np.where(full, cover, 0.0))
    usum = max(u.sum(), 1e-30)
    f = u / usum
    u0 = self._units(np.zeros_like(cover))       # stage-1-only compute
    f0 = u0 / usum
    if plan.alive is None:                       # legacy (non-resilient)
      t_real = wall_ms * f * plan.noise
      if self.replica_of is not None and plan.hedged.any():
        # A hedged shard completes at the earlier of the primary and its
        # replica's reissue — same pricing as the plan-time decision.
        t_hedge = self._hedge_time(wall_ms, u, usum, plan.noise,
                                   plan.noise2)
        t_real = np.where(plan.hedged, np.minimum(t_real, t_hedge),
                          t_real)
      done_full = t_real
    else:
      # Resilient realization: the SAME fault world, draws and backoff
      # delays that made the plan-time decision price the completions —
      # retry r participates only where the plan dispatched it.
      slow = plan.slow
      t_real = wall_ms * f * plan.noise * slow
      t_retry_real = retry_alive = None
      if plan.noise_r is not None:
        t_retry_real = self._retry_times(wall_ms, u, usum, plan.noise,
                                         plan.noise_r, slow, plan.delays)
        retry_alive = plan.alive[self.retry_of]
      done_full = realized_recovery(t_real, t_retry_real, plan.retries,
                                    plan.alive, retry_alive)
    t_stage1 = wall_ms * f0 * plan.noise
    done = np.where(full, done_full,
                    np.where(plan.mode == MODE_STAGE1, t_stage1, 0.0))
    if plan.alive is not None and not self.ccfg.recovery \
        and not plan.alive.all():
      # No-recovery baseline: the frontend has no ladder, so it WAITS on
      # a dead shard until a hard timeout (fault_stall_wait step
      # deadlines) before giving up on its mass — the gather both stalls
      # and drops.
      wait = plan.deadline_ms if np.isfinite(plan.deadline_ms) else wall_ms
      done = np.where(plan.alive, done,
                      self.ccfg.fault_stall_wait * max(wait, wall_ms))
    valid = np.maximum(self.comp_share * self.M, 1.0)
    frac = np.minimum(cover / valid, 1.0)
    acc_c = np.where(
        full, [self.accuracy_fn(x) for x in frac],
        np.where(plan.mode == MODE_STAGE1, self.accuracy_fn(0.0), 0.0))
    step_acc = float(np.sum(self.comp_share * acc_c))
    parallel_ms = float(max(done.max(), 1e-3))
    sharesum = max(self.comp_share.sum(), 1e-30)
    drop_share = float(np.sum(np.where(plan.mode == MODE_DROP,
                                       self.comp_share, 0.0)) / sharesum)
    retried = int(plan.retries.sum()) if plan.retries is not None \
        else int(plan.hedged.sum())
    if plan.alive is not None and not warming:
      self.fault_stats["crash_steps"] += int(not plan.alive.all())
      self.fault_stats["retries"] += retried
      self.fault_stats["stage1_fallbacks"] += int(np.sum(
          (plan.mode == MODE_STAGE1) & ~plan.alive))
      self.fault_stats["dropped"] += int(np.sum(plan.mode == MODE_DROP))
    self.step_idx += 1
    return {"parallel_ms": parallel_ms, "step_acc": step_acc,
            "wall_ms": wall_ms, "gathered": int(full.sum()),
            "hedged": int(plan.hedged.sum()), "comp_ms": done,
            "drop_share": drop_share, "retried": retried}

  def export(self, full_items: int = 100) -> "ClusterMeasuredExport":
    return ClusterMeasuredExport(self, full_items=full_items)


class ClusterMeasuredExport:
  """Measured per-component step latencies for the discrete-event
  simulator — the cluster-tier counterpart of
  `repro.serve.engine.MeasuredStepBackend`.

  ``step_ms_per_component(budget)`` returns the (N,) vector the simulator
  feeds straight into ``ComponentModel.submit(service_ms=...)`` (each
  simulated component indexes its own entry), so hot components serve in
  the time the real tier attributed to them; ``step_ms(budget)`` is the
  frontend-observed parallel completion (max over components).  Budget
  conversion follows MeasuredStepBackend: a simulator budget out of
  ``full_items`` rescales onto the tier's M clusters; the nearest
  measured bucket's predicted wall (a snapshot of the backend's
  control-plane predictor) is attributed by rows read."""

  def __init__(self, backend: ClusterStepBackend, full_items: int = 100):
    self.share = backend.comp_share.copy()
    self.massf = backend.mass_ewma / max(backend.mass_ewma.sum(), 1e-30)
    # Running max over the buckets: a wall measured at a larger budget
    # reads at least as many rows, so a noisy sample below a smaller
    # bucket's is clock noise, not a faster step.
    walls = backend.predictor.table() or {0: 5.0}
    self.walls = dict(zip(sorted(walls), np.maximum.accumulate(
        [walls[b] for b in sorted(walls)])))
    self.M = backend.M
    self.cluster_size = backend.cfg.synopsis.cluster_size
    self.full_items = full_items
    self.n_components = backend.topo.n_components

  def step_ms_per_component(self, budget: int) -> np.ndarray:
    b = budget / max(self.full_items, 1) * self.M
    nearest = min(self.walls, key=lambda x: abs(x - b))
    u = self.share * self.M + b * self.massf * self.cluster_size
    return self.walls[nearest] * u / max(u.sum(), 1e-30)

  def step_ms(self, budget: int) -> float:
    return float(self.step_ms_per_component(budget).max())
