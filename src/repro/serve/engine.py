"""Deadline-driven continuous-batching serving engine (DESIGN.md §8).

This closes the loop the discrete-event simulator (`repro.serving.service`)
only *models*: requests from an arrival trace (`repro.serving.workload`)
occupy slots in a shared synopsis-KV cache, and each decode step picks its
refinement budget with the same `repro.control` latency-control plane the
simulator uses (`DeadlineBudgetPolicy` over a pluggable predictor,
DESIGN.md §10) — except here the predictor is calibrated by **measured**
step wall times, so the accuracy-vs-tail-latency trade comes from the real
kernel path, not a latency model.

Slot lifecycle (DESIGN.md §8): a request is admitted to a free batch lane
(prefill -> synopsis build -> `kv_cache.write_slot`), decodes through
budgeted serve steps shared with the other resident slots (stage 1 always
runs; stage 2 refines the budget's clusters), accumulates its new tokens
in its own recent-ring position (`synopsis_kv.append_recent_slots`), and
retires when its token target is reached — freeing the lane mid-flight
for the next queued request, no lockstep batches.

Compiled-program count stays bounded the same way the simulator assumes:
budgets are bucketed (`DeadlineBudgetPolicy.buckets`), so the engine jits one
serve step per bucket plus one prefill and one build program, all warmed
before the first measured step.

Policies (the simulator's techniques, re-grounded in measured time):

  * ``basic``          — full budget every step, nothing dropped.
  * ``partial``        — full budget, but a request still resident at its
                         deadline is dropped mid-flight (lane freed, its
                         accuracy contribution lost — the paper's skipped
                         partial results), and one finishing late scores 0.
  * ``accuracytrader`` — per-step bucketed budget from the deadline
                         controller against the most urgent resident
                         request's remaining time; stage 1 always lands.
  * ``fixed``          — constant budget (tests/parity runs; ``reissue``
                         only exists in the simulator — replicating a
                         component has no single-host analogue).

`MeasuredStepBackend` exports the engine's measured per-bucket step
latencies back to the simulator (`ScatterGatherService(step_backend=...)`)
so the fleet-scale simulation runs on real component service times.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.control import (CONTRACTS, POLICIES, AccuracyEstimator,
                           AdmissionConfig, AdmissionPolicy,
                           DeadlineBudgetPolicy, TailTracker,
                           make_predictor)
from repro.control.estimator import coverage_profile
from repro.kernels import ops
from repro.models import common as cm
from repro.models import transformer as tf
from repro.serve import corpus_cache as ccache
from repro.serve import kv_cache as kvc
from repro.serve import spans
from repro.serve import synopsis_kv as skv
from repro.serve.corpus_cache import CacheConfig
from repro.serve.prefill import make_extend_step, make_prefill_step
from repro.serve.serve_step import make_serve_step, resolve_impl
from repro.serving.service import _default_concentration
from repro.serving.workload import poisson_arrivals


@dataclasses.dataclass
class EngineConfig:
  """Engine knobs (model shape comes from the ModelConfig)."""
  n_slots: int = 4                 # batch lanes == max resident requests
  prompt_len: int = 128            # tokens per admitted prompt
  max_new_tokens: int = 8          # decode steps per request (<= recent)
  deadline_ms: float = 80.0        # per-request service deadline
  policy: str = "accuracytrader"
  fixed_budget: int = 0            # for policy="fixed"
  impl: Optional[str] = None       # kernel impl; None -> cfg.synopsis.impl
  buckets: Optional[Sequence[int]] = None   # None -> {0, 1, 2, 4, ..., M}
  # Latency-predictor spec for the budget controller (repro.control):
  # "affine" (EW least-squares lat = base + slope*i), "ewma", or
  # "quantile[:pct]" (deadlines target a percentile of the measured
  # per-bucket step times instead of the mean).
  predictor: str = "affine"
  seed: int = 0
  # Overlap admission (prefill+build+write) of new requests with the
  # resident slots' decode step: both are dispatched without an
  # intervening block, so the runtime's async dispatch queue pipelines
  # them (ROADMAP: serialized admission was the saturation point).
  overlap_admission: bool = True
  # Queue-aware predictive admission (DESIGN.md §11,
  # `repro.control.admission`): EDF/least-slack ordering, predictive
  # shed-at-admission and SLO classes.  None = the legacy FIFO queue,
  # bit-identical to the pre-resilience engine.
  admission: Optional[AdmissionConfig] = None
  # Content-addressed corpus cache (DESIGN.md §12,
  # `repro.serve.corpus_cache`): admission consults it before prefill —
  # a hit maps the slot to a shared refcounted arena and skips
  # prefill+build entirely; a strict prefix-extension replays only the
  # KV delta.  None (or capacity 0) = disabled, bit-identical to the
  # pre-cache admission path.
  cache: Optional[CacheConfig] = None
  # ε-or-deadline serving contracts (DESIGN.md §13, `repro.control`
  # CONTRACTS): "deadline" is the legacy behavior (no estimator
  # telemetry, bit-identical to the pre-contract engine);
  # "error_bounded" refines until the online estimator predicts loss
  # <= epsilon and answers early; "deadline_with_bound" keeps the
  # legacy budgets but attaches a calibrated loss confidence band to
  # every answer.
  contract: str = "deadline"
  epsilon: float = 0.02
  band_conf: float = 0.9           # nominal coverage of the loss bands


@dataclasses.dataclass
class EngineRequest:
  rid: int
  arrival_ms: float
  prompt: np.ndarray               # (prompt_len,) int32
  max_new_tokens: int
  # Filled by the engine:
  admit_ms: float = -1.0
  finish_ms: float = -1.0
  # Measured wall of this request's own (blocking) admission — prefill
  # + build + write, or the cache hit's write-only path.  0.0 on the
  # overlapped path, where admissions share one block with the decode
  # step and have no individual wall.
  admit_wall_ms: float = 0.0
  tokens: List[int] = dataclasses.field(default_factory=list)
  budgets: List[int] = dataclasses.field(default_factory=list)
  # Per-step accuracy contributions from a cluster step backend (the
  # scatter-gather tier reports corpus-share-weighted coverage per step;
  # empty on the single-component path, which derives accuracy from
  # ``budgets``).
  step_acc: List[float] = dataclasses.field(default_factory=list)
  accuracy: float = 0.0
  dropped: bool = False            # shed mid-flight (partial execution)
  # -- resilience (DESIGN.md §11) ------------------------------------------
  slo: str = "default"             # SLO class name (admission policy)
  deadline_ms: Optional[float] = None   # per-request deadline override
  shed_admission: bool = False     # refused at admission (zero prefill)
  # Per-step dropped shard-mass fraction from a cluster backend (0 on
  # every step = the request's full corpus answered: available).
  step_drop: List[float] = dataclasses.field(default_factory=list)
  # -- serving contracts (DESIGN.md §13) -----------------------------------
  # Per-step raw online loss estimates + Verdict-style spread proxies
  # (empty under contract="deadline", where no telemetry runs).
  est_raw: List[float] = dataclasses.field(default_factory=list)
  est_spread: List[float] = dataclasses.field(default_factory=list)
  pred_loss: float = -1.0          # calibrated predicted loss at retire
  band_lo: float = 0.0             # loss confidence band (deadline_with_
  band_hi: float = 0.0             # bound / error_bounded)
  # -- the engine's wall clock W (ServingEngine.wall_ms) -------------------
  dispatch_w_ms: float = -1.0      # W at the dispatch of its admission
  first_w_ms: float = -1.0         # W at its first token
  finish_w_ms: float = -1.0        # W at its last token
  # W - now_ms at its dispatch: host time the engine's clock had not
  # counted when it let the request in.
  clock_lag_ms: float = -1.0

  @property
  def latency_ms(self) -> float:
    return self.finish_ms - self.arrival_ms

  @property
  def queue_ms(self) -> float:
    return self.admit_ms - self.arrival_ms


@dataclasses.dataclass
class _Slot:
  req: EngineRequest
  remaining: int


class ServingEngine:
  """Continuous-batching AccuracyTrader engine over the kernel serve path.

  ``accuracy_fn`` maps the fraction of ranked clusters refined in a step
  to result accuracy; the default is the simulator's fig-4 concentration
  curve, so engine and simulator report on the same scale."""

  def __init__(self, cfg: cm.ModelConfig, ecfg: EngineConfig,
               params=None,
               accuracy_fn: Optional[Callable[[float], float]] = None,
               backend=None, estimator: Optional[AccuracyEstimator] = None):
    if kvc.n_attn_positions(cfg) == 0:
      raise ValueError(f"{cfg.name}: no attention positions — nothing to "
                       "synopsize (DESIGN.md §5); use mode='exact' serving")
    C = cfg.synopsis.cluster_size
    if ecfg.prompt_len % C != 0:
      raise ValueError(f"prompt_len {ecfg.prompt_len} % cluster_size {C}")
    if ecfg.max_new_tokens > cfg.synopsis.recent:
      raise ValueError(
          f"max_new_tokens {ecfg.max_new_tokens} > recent ring "
          f"{cfg.synopsis.recent}: a slot's decode residency must fit the "
          "ring (absorb_recent is a whole-cache offline program)")
    if ecfg.policy not in POLICIES:
      raise ValueError(f"policy {ecfg.policy!r} not in {POLICIES}")
    self.cfg = cfg
    self.ecfg = ecfg
    self.M = ecfg.prompt_len // C
    self.impl = resolve_impl(ecfg.impl if ecfg.impl is not None
                             else cfg.synopsis.impl)
    if ecfg.buckets is not None:
      buckets = tuple(sorted({int(b) for b in ecfg.buckets}))
    else:
      buckets = [0]
      b = 1
      while b < self.M:
        buckets.append(b)
        b *= 2
      buckets = tuple(buckets + [self.M])
    if any(b < 0 or b > self.M for b in buckets):
      raise ValueError(f"buckets {buckets} outside [0, M={self.M}]")
    self.buckets = buckets
    if ecfg.policy == "fixed" and ecfg.fixed_budget not in buckets:
      self.buckets = tuple(sorted(set(buckets) | {ecfg.fixed_budget}))
    self.accuracy_fn = accuracy_fn or _default_concentration
    # ε-or-deadline serving contracts (DESIGN.md §13): the online
    # accuracy estimator and the step telemetry feeding it.  One
    # estimator instance per engine unless the caller shares one (the
    # calibration bench fits a single estimator across fixed-budget
    # arms and then serves error_bounded from the same knots).
    if ecfg.contract not in CONTRACTS:
      raise ValueError(f"contract {ecfg.contract!r} not in {CONTRACTS}")
    self.contract = ecfg.contract
    self.estimator = estimator if estimator is not None else \
        AccuracyEstimator(
            floor=max(1.0 - float(self.accuracy_fn(0.0)), 0.0),
            conf=ecfg.band_conf)
    # Telemetry (the stage-1 coverage profile threaded out of the step)
    # only runs under the new contracts: contract="deadline" keeps every
    # legacy step program bit-identical to the pre-contract engine.
    self._telemetry = self.contract != "deadline"
    self._profile_prior: Optional[np.ndarray] = None
    # Optional scatter-gather step backend (repro.serve.cluster,
    # DESIGN.md §9): owns the component cache layout, the per-step gather
    # plan and the measured per-component latency attribution.  Bound
    # BEFORE the policy is built: the budget controller shares the
    # backend's wall predictor (one predictor, one truth — see
    # _make_policy).
    self.backend = backend
    if backend is not None:
      backend.bind(self)
    self.controller = self._make_policy()
    # Queue-aware predictive admission (DESIGN.md §11): deadline
    # resolution, EDF/least-slack ordering, token buckets and
    # shed-at-admission.  None = the legacy FIFO path.
    self.admission = None
    if ecfg.admission is not None:
      self.admission = AdmissionPolicy(ecfg.admission, ecfg.deadline_ms,
                                       self._demand_ms)
    self._admit_ms_ewma = 0.0
    self.prefills = 0
    # Content-addressed corpus cache (DESIGN.md §12): shared arenas keyed
    # on token ids + a model/config fingerprint.  Disabled (capacity 0 /
    # None) it is a pure no-op — every branch below guards on `enabled`.
    self.corpus_cache = ccache.CorpusCache(
        ecfg.cache,
        fingerprint=ccache.corpus_fingerprint(cfg, self.impl,
                                              ecfg.prompt_len, ecfg.seed))
    from repro.kernels.quant import parse_qconfig  # noqa: PLC0415
    # Delta replay re-attends over the cached corpus k/v; the "+kv"
    # quantized arenas store those rows as int8 blocks whose scales are
    # cluster-granular, so the extension path would need a dequantized
    # materialization — disable extends and take plain hits/misses.
    self._delta_ok = (ccache.supports_delta(cfg)
                      and not parse_qconfig(
                          getattr(cfg.synopsis, "quant", "none")).sorted_kv)
    self._slot_entry: List[Optional[str]] = [None] * ecfg.n_slots
    # Fleet tier (DESIGN.md §14): one admission maps the arena onto R
    # replica rows and each mapping holds its own pin, so retiring one
    # replica's mapping can never free an arena another still reads.
    self._map_count = int(getattr(backend, "replica_mappings", 1)) \
        if backend is not None else 1

    if params is None:
      params = tf.init_params(jax.random.PRNGKey(ecfg.seed), cfg)
    self.params = params

    self._prefill = jax.jit(make_prefill_step(cfg, impl=self.impl))
    self._build = jax.jit(lambda c: skv.build(c, cfg, impl=self.impl))
    # Delta-replay programs (prefix-extension cache hits): jitted lazily
    # on the first extend admission; jax re-specializes per (P, E) shape.
    self._extend = jax.jit(make_extend_step(cfg, impl=self.impl)) \
        if self._delta_ok else None
    self._extend_build = jax.jit(
        lambda a, k, v: skv.extend_synopsis(a, k, v, cfg, impl=self.impl))
    self._bx = kvc.slot_batch_axes(cfg, ecfg.n_slots, ecfg.prompt_len,
                                   synopsis=True)
    bx = self._bx
    if backend is not None:
      self._write = backend.write_slot
    else:
      self._write = jax.jit(
          lambda cache, sub, slot: kvc.write_slot(cache, sub, slot, bx))
    self._append = jax.jit(skv.append_recent_slots)
    self._step_cache: Dict[int, Callable] = {}
    self._warming = False
    self._warm_syn = None

    self.reset()
    self._warmup()

  def _make_policy(self) -> DeadlineBudgetPolicy:
    """The engine's slice of the control plane: one DeadlineBudgetPolicy
    whose predictor is calibrated by measured step wall times.

    With a cluster backend the policy REUSES the backend's wall
    predictor instead of fitting its own affine model (one predictor,
    one truth): the backend observes the raw program wall per bucket in
    ``account`` — a conservative upper bound on the parallel completion
    the clock advances by — and the budget controller's slow-start
    handles its non-extrapolating bucket table.  The engine then never
    observes the predictor itself (see ``_decode_step``): one
    observation stream, no double counting."""
    e = self.ecfg
    shared = getattr(self.backend, "predictor", None) \
        if self.backend is not None else None
    if shared is not None:
      pred = shared
    else:
      kw = {"base": 2.0, "slope": 0.5, "alpha": 0.1} \
          if e.predictor.startswith("affine") else {}
      pred = make_predictor(e.predictor, **kw)
    return DeadlineBudgetPolicy(
        policy=e.policy, buckets=self.buckets, i_max_cap=self.M,
        predictor=pred, fixed_budget=e.fixed_budget,
        contract=self.contract, epsilon=e.epsilon,
        estimator=self.estimator)

  # -- state ----------------------------------------------------------------
  def reset(self, reset_controller: bool = False) -> None:
    """Fresh slots/cache/clock for a new measurement window.  The latency
    model persists across windows by default (as in the simulator's
    ``run_open_loop``)."""
    e = self.ecfg
    if self.backend is not None:
      self.cache = self.backend.zeros_cache()
    else:
      self.cache = kvc.zeros_cache(self.cfg, e.n_slots, e.prompt_len,
                                   synopsis=True)
    self.tok = jnp.zeros((e.n_slots, 1), jnp.int32)
    self.slots: List[Optional[_Slot]] = [None] * e.n_slots
    self.now_ms = 0.0
    # The walls the clock advanced by that the engine measured itself
    # (step walls, serial admission walls), and run()'s start and end on
    # perf_counter: together they give wall_ms().
    self.busy_ms = 0.0
    self._t_run: Optional[float] = None
    self._t_end: Optional[float] = None
    # XLA programs built or fetched from the persistent cache while a
    # run() outside warm-up was open.
    self.compiles = 0
    self.completed: List[EngineRequest] = []
    self.events: List[Tuple[str, int, int, float]] = []
    self.step_log: List[Tuple[int, float, int]] = []   # (budget, ms, active)
    self.prefills = 0
    # The corpus cache persists across windows like the latency model
    # (warm arenas are the point); only the per-window counters and the
    # retiring slots' pins reset.
    for key in getattr(self, "_slot_entry", []):
      if key is not None:
        self.corpus_cache.release(key, self._map_count)
    self._slot_entry = [None] * e.n_slots
    self.corpus_cache.reset_stats()
    # Per-window contract telemetry resets; the estimator's calibration
    # and the coverage-profile prior persist like the latency model.
    self._slot_profile: List[Optional[np.ndarray]] = [None] * e.n_slots
    self._freed_log: List[int] = []
    if getattr(self, "admission", None) is not None:
      self.admission.reset()
    if reset_controller:
      self.controller = self._make_policy()

  def _step_fn(self, budget: int):
    if budget not in self._step_cache:
      if self.backend is not None:
        self._step_cache[budget] = self.backend.step_fn(budget)
      else:
        attn = self._telemetry_attention if self._telemetry else None
        self._step_cache[budget] = jax.jit(make_serve_step(
            self.cfg, mode="synopsis", i_max=budget, impl=self.impl,
            attention_fn=attn))
    return self._step_cache[budget]

  def _telemetry_attention(self, q, csl, *, i_max, cluster_size, sm_scale,
                           cap=None, self_kv=None, impl="xla"):
    """Single-component synopsis decode attention with the stage-1
    coverage profile (DESIGN.md §13) threaded out as aux telemetry.
    Mirrors `ops.synopsis_cache_attention` stage for stage — same
    kernels, same selection, same merge — so tokens stay bit-identical
    to the non-telemetry path (ε=0 parity is asserted in
    tests/test_estimator.py); the profile reuses the stage-1 scores the
    step already computed, no extra passes over KV."""
    B = q.shape[0]
    Hkv, M = csl["k_syn"].shape[1], csl["k_syn"].shape[2]
    scores, p_syn = ops.synopsis_stage1(
        q, csl["k_syn"], csl["v_syn"], csl["counts"], sm_scale=sm_scale,
        cap=cap, impl=impl)
    if i_max > 0:
      _, selected = jax.lax.top_k(scores, min(i_max, M))
      selected = selected.astype(jnp.int32)
    else:
      selected = jnp.full((B, Hkv, 1), -1, jnp.int32)
    extras = ops.build_extras(csl.get("recent_k"), csl.get("recent_v"),
                              csl.get("recent_len"), self_kv)
    p_ref = ops.refine_stage2(
        q, csl["k"], csl["v"], selected, csl["k_syn"], csl["v_syn"],
        csl["counts"], cluster_size=cluster_size, sm_scale=sm_scale,
        cap=cap, impl=impl, extras=extras)
    out, _, _ = ops.merge_partials(p_syn, p_ref)
    return out, {"est_profile": coverage_profile(scores, csl["counts"])}

  def _warm_buckets(self) -> Sequence[int]:
    p = self.ecfg.policy
    # error_bounded can answer early at ANY bucket (the estimator's
    # min with the policy base), so every bucket's program must be warm.
    if p == "accuracytrader" or self.contract == "error_bounded":
      return self.buckets
    if p == "fixed":
      return (self.ecfg.fixed_budget,)
    return (self.M,)

  def _warmup(self) -> None:
    """Compile every program the run can dispatch (one serve step per
    bucket + prefill + build + the slot writes) by driving the *real*
    admit/step paths on a dummy request, so measured latencies are
    steady-state from the first trace request; warmup state is then
    discarded and never observed by the controller.  The ``compiles``
    counter holds a window to that: it reads 0 after warm-up
    (tests/test_spans.py::test_warm_window_compiles_nothing).

    Each bucket is driven TWICE, re-writing the warm slot in between:
    a step consuming a freshly *written* cache and one consuming the
    previous step's *append*-produced cache are distinct jit signatures
    (output shardings/layouts differ, especially with a shard_map-ing
    backend), and an unwarmed signature would recompile mid-window and
    pollute the first measured latencies."""
    self._warming = True
    warm = self._warm_buckets()
    req = EngineRequest(rid=-1, arrival_ms=0.0,
                        prompt=np.zeros((self.ecfg.prompt_len,), np.int32),
                        max_new_tokens=2 * len(warm) + 1)
    self._admit(req, 0)
    for i, b in enumerate(warm):
      self._decode_step([0], budget=b)     # post-write cache lineage
      self._decode_step([0], budget=b)     # post-append cache lineage
      if i < len(warm) - 1:
        self.cache = self._write(self.cache, self._warm_syn, 0)
    # A throwaway mini-window through the real run() loop: admission
    # bursts, retire/re-admit and the post-retire step compose cache
    # lineages the enumeration above cannot, and any leftover signature
    # must compile NOW, not inside the first measured window.  Arrivals
    # are STAGGERED (not all at t=0) so later requests land while a
    # resident slot is decoding — that drives the overlapped-admission
    # path, whose step-reads-pre-admission-cache / append-onto-written-
    # cache composition is its own jit signature.
    self.reset()
    mini = [EngineRequest(
        rid=-2 - i, arrival_ms=float(i),
        prompt=np.zeros((self.ecfg.prompt_len,), np.int32),
        max_new_tokens=min(2, self.ecfg.max_new_tokens))
        for i in range(min(2, self.ecfg.n_slots) + 1)]
    self.run(mini)
    self._warm_syn = None
    self._warming = False
    self.reset()

  # -- scheduling -----------------------------------------------------------
  def _dispatch_admission(self, req: EngineRequest, slot: int, cache):
    """Dispatch one admission's prefill -> build -> slot-write chain
    WITHOUT blocking; returns (first-token array, written cache).  Both
    the serial and the overlapped admission paths go through here.

    With the corpus cache enabled (DESIGN.md §12) the chain is consulted
    first: an exact hit skips prefill AND build — only the slot write is
    dispatched, mapping the lane onto the shared arena (the private
    recent-ring half is zeros in the arena, so the lane starts its own
    copy-on-write decode state); a strict prefix-extension replays only
    the extension's KV delta; a miss runs the full chain and publishes
    the arena for subsequent admissions.  Warmup bypasses the cache
    entirely — its dummy all-zero prompts would otherwise alias one
    corpus and skip compiling the prefill/build programs."""
    req.dispatch_w_ms = self.wall_ms()
    req.clock_lag_ms = req.dispatch_w_ms - self.now_ms
    cc = self.corpus_cache
    use_cache = cc.enabled and not self._warming
    if use_cache:
      with spans.span("engine.admit.lookup"):
        kind, entry = cc.lookup(req.prompt, allow_extend=self._delta_ok)
      if kind == "hit":
        cc.acquire(entry, self._map_count)
        self._slot_entry[slot] = entry.key
        with spans.span("engine.admit.write"):
          return entry.first_token, self._write(cache, entry.arena, slot)
      if kind == "extend":
        with spans.span("engine.admit.extend"):
          first, new_entry = self._delta_admit(entry, req.prompt)
        if self._map_count > 1:       # publish holds the first mapping
          cc.acquire(new_entry, self._map_count - 1)
        self._slot_entry[slot] = new_entry.key
        with spans.span("engine.admit.write"):
          return first, self._write(cache, new_entry.arena, slot)
    self.prefills += 1
    with spans.span("engine.admit.prefill"):
      prompt = jnp.asarray(req.prompt, jnp.int32)[None]
      logits, cache1 = self._prefill(self.params, prompt)
    with spans.span("engine.admit.build"):
      syn = self._build(cache1)
    if self._warming:
      self._warm_syn = syn       # reused to warm re-write cache lineages
    first = jnp.argmax(logits, -1).astype(jnp.int32)          # (1,)
    if use_cache:
      entry = cc.publish(req.prompt, syn, first)
      if self._map_count > 1:         # publish holds the first mapping
        cc.acquire(entry, self._map_count - 1)
      self._slot_entry[slot] = entry.key
    with spans.span("engine.admit.write"):
      return first, self._write(cache, syn, slot)

  def _delta_admit(self, entry, prompt) -> Tuple[jax.Array, object]:
    """Prefix-extension replay: run only the extension tokens against the
    cached arena's sorted prefix KV (`prefill.make_extend_step`), grow
    the synopsis incrementally (`synopsis_kv.extend_synopsis`), publish
    the extended corpus as its own entry.  No full prefill is dispatched
    — ``self.prefills`` does not move; the cache counts it as a
    delta hit."""
    t = np.asarray(prompt, np.int32)
    L = int(entry.tokens.shape[0])
    ext = jnp.asarray(t[L:], jnp.int32)[None]
    logits, (k_new, v_new) = self._extend(
        self.params, ext, entry.arena["k"], entry.arena["v"],
        jnp.int32(L))
    arena = self._extend_build(entry.arena, k_new, v_new)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return first, self.corpus_cache.publish(t, arena, first)

  def _admit(self, req: EngineRequest, slot: int) -> None:
    with spans.span("engine.admit", rid=req.rid, slot=slot, overlapped=0):
      # queue_ms measures pure waiting: the clock *before* this request's
      # own prefill+build advances it.
      req.admit_ms = self.now_ms
      t0 = time.perf_counter()
      first, self.cache = self._dispatch_admission(req, slot, self.cache)
      self.tok = self.tok.at[slot, 0].set(first[0])
      with spans.span("engine.admit.sync"):
        jax.block_until_ready((self.cache, self.tok))
      dt = (time.perf_counter() - t0) * 1e3
      self.now_ms += dt
      self.busy_ms += dt
      req.admit_wall_ms = dt
      # Admission-cost EWMA: the fixed part of the demand estimate the
      # predictive shed uses (_demand_ms).
      if not self._warming:
        self._admit_ms_ewma = dt if self._admit_ms_ewma == 0.0 \
            else 0.7 * self._admit_ms_ewma + 0.3 * dt
      req.tokens.append(int(first[0]))
      self._slot_profile[slot] = None
      self.slots[slot] = _Slot(req, req.max_new_tokens)
      req.first_w_ms = self.wall_ms()
      self.events.append(("admit", req.rid, slot, self.now_ms))

  def _pick_budget(self, active: Sequence[int],
                   extra: Sequence[EngineRequest] = ()) -> int:
    """``extra``: requests being admitted concurrently with this step
    (admission overlap) — not decoding yet, but the step stands between
    them and their first token, so their deadlines clamp the budget the
    same way they would on the serial path."""
    e = self.ecfg
    remaining = 0.0
    if e.policy == "accuracytrader":
      remaining = min(
          [self._abs_deadline(self.slots[i].req) - self.now_ms
           for i in active] +
          [self._abs_deadline(r) - self.now_ms for r in extra])
    if self.contract == "error_bounded":
      granted, base = self.controller.budget_for_contract(
          max(remaining, 0.0),
          profiles=[self._request_profile(i) for i in active])
      if not self._warming:
        self._freed_log.append(base - granted)
      return granted
    return self.controller.budget_for(max(remaining, 0.0))

  def _request_profile(self, slot: int) -> np.ndarray:
    """Latest measured coverage profile for the request in ``slot``: its
    own last step's profile when one exists, else the EWMA prior over
    recent steps (a freshly admitted request has not scored its synopsis
    yet), else the uniform profile — every cluster equally useful, the
    most conservative monotone assumption."""
    p = self._slot_profile[slot]
    if p is not None:
      return p
    if self._profile_prior is not None:
      return self._profile_prior
    return np.linspace(0.0, 1.0, self.M + 1)

  def _deadline_of(self, req: EngineRequest) -> float:
    """Per-request deadline: explicit override > SLO class (admission
    policy) > the engine default — one resolution rule everywhere
    (budget, step deadline, partial shed, summary accounting)."""
    if self.admission is not None:
      return self.admission.deadline_for(req)
    if req.deadline_ms is not None:
      return float(req.deadline_ms)
    return self.ecfg.deadline_ms

  def _abs_deadline(self, req: EngineRequest) -> float:
    return req.arrival_ms + self._deadline_of(req)

  def _demand_ms(self, req: EngineRequest) -> float:
    """Lower-bound service-demand estimate at arrival (the predictive
    shed's input): the admission-cost EWMA plus one smallest-bucket
    (stage-1-only) predicted step wall per decode token.  A lower bound
    by construction — real steps only refine MORE — so at low load no
    feasible request is ever shed (tests/test_resilience.py)."""
    floor = self.controller.predictor.predict(self.buckets[0])
    return self._admit_ms_ewma + req.max_new_tokens * floor

  def _retire(self, slot: int) -> None:
    s = self.slots[slot]
    req = s.req
    with spans.span("engine.retire", rid=req.rid, slot=slot):
      req.finish_ms = self.now_ms
      # Unpin the slot's shared-arena mapping (the entry stays resident,
      # warm for the next admission, until capacity pressure evicts it).
      if self._slot_entry[slot] is not None:
        self.corpus_cache.release(self._slot_entry[slot], self._map_count)
        self._slot_entry[slot] = None
      req.dropped = s.remaining > 0      # shed mid-flight, not finished
      e = self.ecfg
      # With a cluster backend, each step reported the corpus-share-weighted
      # accuracy of its gather (components refined / stage-1 floor / skipped).
      stepwise = float(np.mean(req.step_acc)) if req.step_acc else None
      if e.policy == "basic":
        req.accuracy = stepwise if stepwise is not None else 1.0
      elif e.policy == "partial":
        # Partial execution: a result missing at the deadline is skipped —
        # its entire accuracy contribution is lost (paper §5).
        if req.dropped or req.latency_ms > self._deadline_of(req):
          req.accuracy = 0.0
        else:
          req.accuracy = stepwise if stepwise is not None else 1.0
      elif stepwise is not None:
        req.accuracy = stepwise
      else:
        # Stage 1 always landed; each step covered budget/M of the ranked
        # clusters exactly plus the synopsis estimate of the rest.
        fr = [min(b, self.M) / self.M for b in req.budgets] or [0.0]
        req.accuracy = float(np.mean([self.accuracy_fn(f) for f in fr]))
      # Contract outputs (DESIGN.md §13): the calibrated loss prediction
      # and its confidence band, from the request's own step telemetry.
      if self._telemetry and req.est_raw:
        raw = float(np.mean(req.est_raw))
        req.pred_loss = float(self.estimator.predict(raw))
        req.band_lo, req.band_hi = self.estimator.band(
            raw, spread=float(np.mean(req.est_spread)))
      self._slot_profile[slot] = None
      self.slots[slot] = None
      self.completed.append(req)
      req.finish_w_ms = self.wall_ms()
      self.events.append(("retire", req.rid, slot, self.now_ms))

  def _step_deadline(self, active: Sequence[int]) -> float:
    """Per-step deadline slice for the cluster frontend's gather decision:
    the most urgent resident request's remaining time, spread over its
    remaining decode steps."""
    vals = [max(self._abs_deadline(self.slots[i].req) - self.now_ms,
                0.0) / max(self.slots[i].remaining, 1) for i in active]
    return min(vals) if vals else float("inf")

  def _decode_step(self, active: Sequence[int],
                   budget: Optional[int] = None,
                   write_cache=None) -> None:
    """One budgeted decode step for the ``active`` slots.  ``write_cache``
    (admission overlap) supplies the cache the step's updates land on:
    the step itself reads the pre-admission cache — active lanes are
    identical in both — while freshly admitted lanes ride in via the
    write chain, all blocked once."""
    with spans.span("engine.decode_step", step=len(self.step_log)):
      if budget is None:
        with spans.span("engine.step.budget"):
          budget = self._pick_budget(active)
      e = self.ecfg
      plan = None
      if self.backend is not None:
        deadline = self._step_deadline(active) if not self._warming \
            else float("inf")
        plan = self.backend.plan_step(budget, deadline)
      step = self._step_fn(budget)
      t0 = time.perf_counter()
      with spans.span("engine.step.dispatch", budget=budget,
                      active=len(active)):
        if plan is not None:
          logits, st = step(self.params, self.cache, self.tok, plan.fe_mode)
        else:
          logits, st = step(self.params, self.cache, self.tok)
        new_tok = jnp.argmax(logits, -1).astype(jnp.int32)      # (n_slots,)
        mask = np.zeros((self.ecfg.n_slots,), bool)
        mask[list(active)] = True
        amask = jnp.asarray(mask)
        target = write_cache if write_cache is not None else self.cache
        self.cache = self._append(target, st["k_delta"], st["v_delta"],
                                  amask)
        self.cache["pos"] = jnp.where(amask, st["pos"], self.cache["pos"])
        # Hybrid archs: SSM decode state advances every step too (per-slot).
        for name in ("conv_state", "ssd_state"):
          if name in st:
            shape = [1] * self.cache[name].ndim
            shape[self._bx[name]] = self.ecfg.n_slots
            m = amask.reshape(shape)
            self.cache[name] = jnp.where(m, st[name], self.cache[name])
        self.tok = jnp.where(amask[:, None], new_tok[:, None], self.tok)
      with spans.span("engine.step.sync"):
        jax.block_until_ready((self.cache, self.tok))
      dt = (time.perf_counter() - t0) * 1e3
      step_acc = None
      step_drop = None
      if plan is not None:
        info = self.backend.account(budget, dt, plan, st,
                                    warming=self._warming)
        dt = info["parallel_ms"]       # the frontend-observed completion
        step_acc = info["step_acc"]
        step_drop = info.get("drop_share")
      self.now_ms += dt
      self.busy_ms += dt
      # With a cluster backend the shared predictor was already calibrated
      # inside account (one predictor, one observation stream); the engine
      # only observes its own predictor on the single-component path.
      if self.ecfg.policy == "accuracytrader" and not self._warming \
          and write_cache is None and self.backend is None:
        self.controller.observe(budget, dt)
      self.step_log.append((budget, dt, len(active)))
      # Contract telemetry (DESIGN.md §13): the per-layer coverage
      # profiles threaded out of the scan, averaged over layers — this
      # step's measured signal for next step's ε decision and for each
      # request's running raw-loss estimate.
      prof = None
      if self._telemetry and "est_profile" in st:
        prof = np.asarray(st["est_profile"], np.float64)
        prof = prof.reshape(-1, self.ecfg.n_slots, prof.shape[-1]).mean(0)
        for i in active:
          self._slot_profile[i] = prof[i]
        mean_prof = prof[list(active)].mean(0)
        self._profile_prior = mean_prof if self._profile_prior is None \
            else 0.7 * self._profile_prior + 0.3 * mean_prof
      with spans.span("engine.step.tokens"):
        toks = np.asarray(new_tok)
        for i in active:
          s = self.slots[i]
          s.req.tokens.append(int(toks[i]))
          s.req.budgets.append(budget)
          if step_acc is not None:
            s.req.step_acc.append(step_acc)
          if step_drop is not None:
            s.req.step_drop.append(step_drop)
          if prof is not None:
            s.req.est_raw.append(self.estimator.raw_loss(prof[i], budget))
            s.req.est_spread.append(
                self.estimator.spread_from_profile(prof[i], budget))
          s.remaining -= 1
          if s.remaining <= 0:
            self._retire(i)

  # -- driving --------------------------------------------------------------
  def wall_ms(self) -> float:
    """W: the window's time on a server that waits for its arrivals.
    ``now_ms`` jumps idle time to the next arrival, and advances by the
    walls the engine measured (``busy_ms``); W adds the host time that
    passed outside those walls since ``run()`` began, which ``now_ms``
    never counts.  After the run it stays at the run's end."""
    if self._t_run is None:
      return self.now_ms
    end = self._t_end if self._t_end is not None else time.perf_counter()
    return self.now_ms + (end - self._t_run) * 1e3 - self.busy_ms

  def run(self, requests: Sequence[EngineRequest]) -> Dict[str, float]:
    """Drive the engine over an arrival trace; returns the window summary.

    The clock is hybrid: arrivals advance on the trace's clock, service
    advances by *measured* wall time of each dispatched program — so
    queueing delay under load is real, not modelled."""
    self._t_run, self._t_end = time.perf_counter(), None
    pending = collections.deque(
        sorted(requests, key=lambda r: (r.arrival_ms, r.rid)))
    loop = self._run_fifo if self.admission is None else self._run_admission

    def count(event, _secs, **_):
      if event == spans.COMPILE_EVENT:
        self.compiles += 1

    listen = not self._warming
    if listen:
      jax.monitoring.register_event_duration_secs_listener(count)
    try:
      loop(pending)
    finally:
      self._t_end = time.perf_counter()
      if listen:
        jax.monitoring.unregister_event_duration_listener(count)
    return self.summary()

  def _run_fifo(self, pending) -> None:
    """The ``run`` loop without an admission policy: arrivals admitted in
    order as lanes free."""
    while pending or any(s is not None for s in self.slots):
      if self.ecfg.policy == "partial":
        # Partial execution sheds unfinished work AT the deadline: the
        # result is skipped (accuracy 0 via _retire) and the lane frees
        # for the queue — a doomed request must not keep burning steps.
        for i, s in enumerate(self.slots):
          if s is not None and self.now_ms >= self._abs_deadline(s.req):
            self._retire(i)
      # Every arrived request that fits a free lane is admitted this
      # iteration — overlapped with the residents' decode step when
      # possible, else serially.
      free = [i for i, s in enumerate(self.slots) if s is None]
      admissions = []
      while free and pending and pending[0].arrival_ms <= self.now_ms:
        admissions.append((pending.popleft(), free.pop(0)))
      active = [i for i, s in enumerate(self.slots) if s is not None]
      # Overlap applies to the local single-component path only: the
      # cluster backend advances the clock by the *modelled parallel*
      # step completion, which would hide the admissions' real wall time
      # if they were folded into the same measured window.
      if admissions and active and self.ecfg.overlap_admission \
          and self.backend is None:
        self._admit_overlapped(admissions, active)
        continue
      for req, slot in admissions:
        self._admit(req, slot)
      active = [i for i, s in enumerate(self.slots) if s is not None]
      if not active:
        if not pending:
          break
        # Idle: jump to the next arrival.
        self.now_ms = max(self.now_ms, pending[0].arrival_ms)
        continue
      self._decode_step(active)

  def _shed(self, req: EngineRequest) -> None:
    """Refuse a request at admission (predicted dead, DESIGN.md §11):
    zero prefill, zero decode steps, the lane goes to a request that can
    still make its deadline.  Scores 0 accuracy and counts as dropped —
    the same book-keeping as a mid-flight partial-execution shed, minus
    all the burned work."""
    req.finish_ms = max(self.now_ms, req.arrival_ms)
    req.dropped = True
    req.shed_admission = True
    req.accuracy = 0.0
    self.completed.append(req)
    self.events.append(("shed", req.rid, -1, self.now_ms))

  def _run_admission(self, pending) -> None:
    """The ``run`` loop under an :class:`AdmissionPolicy` (DESIGN.md
    §11): arrivals land in a *ready* queue; each iteration rate-gates
    them (token bucket per SLO class — over-rate requests WAIT, they are
    not shed), sheds the predicted-dead (now + estimated demand already
    past the deadline), orders the survivors by the configured key
    (EDF / least-slack / FIFO) and admits into free lanes.  Everything
    downstream (decode, retire, overlap) is the standard path."""
    ready: List[EngineRequest] = []
    while pending or ready or any(s is not None for s in self.slots):
      if self.ecfg.policy == "partial":
        for i, s in enumerate(self.slots):
          if s is not None and self.now_ms >= self._abs_deadline(s.req):
            self._retire(i)
      while pending and pending[0].arrival_ms <= self.now_ms:
        ready.append(pending.popleft())
      kept, gated = [], []
      for r in ready:
        if not self.admission.rate_admit(r, self.now_ms):
          gated.append(r)           # waits for its class's token bucket
        elif self.admission.predicted_dead(r, self.now_ms):
          self._shed(r)
        else:
          kept.append(r)
      kept.sort(key=lambda r: self.admission.key(r, self.now_ms))
      free = [i for i, s in enumerate(self.slots) if s is None]
      admissions = []
      while free and kept:
        admissions.append((kept.pop(0), free.pop(0)))
      ready = kept + gated
      active = [i for i, s in enumerate(self.slots) if s is not None]
      if admissions and active and self.ecfg.overlap_admission \
          and self.backend is None:
        self._admit_overlapped(admissions, active)
        continue
      for req, slot in admissions:
        self._admit(req, slot)
      active = [i for i, s in enumerate(self.slots) if s is not None]
      if not active:
        if ready:
          # Only rate-gated requests remain resident (every eligible one
          # was admitted — all lanes were free): advance until their
          # token bucket refills (1 ms quanta keep this deterministic).
          self.now_ms += 1.0
        elif pending:
          self.now_ms = max(self.now_ms, pending[0].arrival_ms)
        else:
          break
        continue
      self._decode_step(active)

  def _admit_overlapped(self, admissions, active: Sequence[int]) -> None:
    """Admission/decode overlap (ROADMAP Perf): dispatch the admitted
    requests' prefill + synopsis build + slot writes WITHOUT blocking,
    dispatch the residents' decode step behind them (the step reads the
    pre-admission cache; its updates land on the written one), and block
    once for the whole window inside ``_decode_step`` — the runtime's
    async dispatch queue pipelines admission with decode instead of
    serializing a blocking admit per request."""
    t_admit = self.now_ms
    budget = self._pick_budget(active, extra=[r for r, _ in admissions])
    cache_adm = self.cache
    firsts = []
    for req, slot in admissions:
      with spans.span("engine.admit", rid=req.rid, slot=slot, overlapped=1):
        req.admit_ms = t_admit
        first, cache_adm = self._dispatch_admission(req, slot, cache_adm)
      firsts.append(first)
    self._decode_step(active, budget=budget, write_cache=cache_adm)
    for (req, slot), first in zip(admissions, firsts):
      with spans.span("engine.admit", rid=req.rid, slot=slot, overlapped=1):
        self.tok = self.tok.at[slot, 0].set(first[0])
        req.tokens.append(int(first[0]))
        self._slot_profile[slot] = None
        self.slots[slot] = _Slot(req, req.max_new_tokens)
        req.first_w_ms = self.wall_ms()
        self.events.append(("admit", req.rid, slot, self.now_ms))

  def _class_stats(self, reqs: Sequence[EngineRequest]) -> Dict[str, float]:
    """Accounting over one request subset; latency percentiles and
    accuracy cover *served* requests only (an admission-shed request has
    no service latency — it was never served), while shed/goodput cover
    the whole subset, so per-class stats sum to the aggregate."""
    served = [r for r in reqs if not r.shed_admission]
    tracker = TailTracker()
    for r in served:
      tracker.observe(r.latency_ms)
    s = tracker.summary()
    accs = [r.accuracy for r in served]
    s["accuracy_loss_pct"] = 100.0 * (1.0 - float(np.mean(accs))) \
        if accs else 0.0
    s["deadline_miss_pct"] = 100.0 * float(np.mean(
        [r.latency_ms > self._deadline_of(r) for r in served])) \
        if served else 0.0
    s["queue_p99"] = float(np.percentile(
        [r.queue_ms for r in served], 99)) if served else 0.0
    s["shed_pct"] = 100.0 * float(np.mean(
        [r.dropped for r in reqs])) if reqs else 0.0
    s["shed_admission_n"] = sum(r.shed_admission for r in reqs)
    s["served_n"] = len(served)
    # Goodput: requests actually answered within their own deadline.
    s["goodput_n"] = sum(1 for r in served if not r.dropped
                         and r.latency_ms <= self._deadline_of(r))
    # Availability: a served request whose every step answered its full
    # shard mass (no component dropped — stage-1 fallback still counts
    # as answered; DESIGN.md §11).
    s["availability_pct"] = 100.0 * float(np.mean(
        [not r.dropped and all(d <= 0.0 for d in r.step_drop)
         for r in served])) if served else 100.0
    for p in (10, 50, 90):
      s[f"acc_p{p}"] = float(np.percentile(accs, p)) if accs else 0.0
    return s

  def summary(self) -> Dict[str, float]:
    s = self._class_stats(self.completed)
    s["mean_budget"] = float(np.mean([b for b, _, _ in self.step_log])) \
        if self.step_log else 0.0
    s["steps"] = len(self.step_log)
    s["prefills"] = self.prefills
    # Host time the engine's clock had not counted by the window's end
    # (W - now_ms), and the programs compiled inside the window.
    s["clock_lag_ms"] = self.wall_ms() - self.now_ms
    s["compiles"] = self.compiles
    # Per-request admission wall percentiles (serial admissions only —
    # the overlapped path shares one block with the decode step and has
    # no per-request wall).  The hit-vs-miss gap here is the corpus
    # cache's headline number (BENCH_cache.json).
    walls = [r.admit_wall_ms for r in self.completed
             if not r.shed_admission and r.admit_wall_ms > 0.0]
    s["admission_p50"] = float(np.percentile(walls, 50)) if walls else 0.0
    s["admission_p99"] = float(np.percentile(walls, 99)) if walls else 0.0
    if self.corpus_cache.enabled:
      cst = self.corpus_cache.stats()
      for name in ("hits", "misses", "delta_hits", "evictions", "entries",
                   "bytes"):
        s[f"cache_{name}"] = float(cst[name])
      s["cache_hit_rate"] = float(cst["hit_rate"])
    s["goodput_per_s"] = s["goodput_n"] / (self.now_ms / 1e3) \
        if self.now_ms > 0 else 0.0
    # Contract accounting (DESIGN.md §13): prediction quality against
    # the measured loss, band coverage at the stated confidence, and the
    # budget error_bounded freed per step vs the policy's base grant.
    if self._telemetry:
      served = [r for r in self.completed
                if not r.shed_admission and r.est_raw]
      preds = np.asarray([r.pred_loss for r in served], np.float64)
      meas = np.asarray([1.0 - r.accuracy for r in served], np.float64)
      s["pred_loss_mean"] = float(preds.mean()) if len(preds) else 0.0
      s["pred_loss_mae"] = float(np.abs(preds - meas).mean()) \
          if len(preds) else 0.0
      s["band_cover_pct"] = 100.0 * float(np.mean(
          [r.band_lo - 1e-9 <= m <= r.band_hi + 1e-9
           for r, m in zip(served, meas)])) if served else 0.0
      s["freed_budget_mean"] = float(np.mean(self._freed_log)) \
          if self._freed_log else 0.0
    # Per-SLO-class breakdown (DESIGN.md §11): every completed request
    # belongs to exactly one class, so the per-class counts partition the
    # aggregate (tests/test_resilience.py asserts the sums).
    names = sorted({r.slo for r in self.completed})
    if names != ["default"] and names:
      s["classes"] = {
          name: self._class_stats([r for r in self.completed
                                   if r.slo == name])
          for name in names}
    return s

  # -- probes ---------------------------------------------------------------
  def probe_step_ms(self, budget: int, iters: int = 3) -> float:
    """Median measured latency of one bucketed serve step on the current
    resident cache (state is not mutated) — the calibration source for
    :class:`MeasuredStepBackend`."""
    if budget not in self.buckets:
      raise ValueError(f"budget {budget} not a bucket {self.buckets}")
    step = self._step_fn(budget)
    args = (self.params, self.cache, self.tok)
    if self.backend is not None:
      args = args + (self.backend.full_mode(),)
    jax.block_until_ready(step(*args))
    ts = []
    for _ in range(iters):
      t0 = time.perf_counter()
      jax.block_until_ready(step(*args))
      ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))


class MeasuredStepBackend:
  """Measured per-bucket step latencies for the discrete-event simulator.

  The simulator's ``accuracytrader`` technique can delegate component
  service times to this table (``ScatterGatherService(step_backend=...)``):
  a component "processing i ranked clusters" then costs what the real
  kernel path *measured* for the corresponding budget bucket, closing the
  simulated-time -> measured-time loop (DESIGN.md §8).

  Budget units differ between the stacks: the simulator budgets clusters
  out of ``ServiceConfig.full_items`` (default 100), the engine out of
  its M = prompt_len / cluster_size.  ``full_items`` sets the conversion
  — a simulator budget of ``i`` costs what the engine measured at the
  bucket nearest ``i / full_items * M``, so the measured latency *slope*
  over the budget range survives the translation instead of collapsing
  onto the top engine bucket."""

  def __init__(self, engine: ServingEngine, iters: int = 3,
               full_items: int = 100):
    self.buckets = engine.buckets
    self.M = engine.M
    self.full_items = full_items
    self.table = {b: engine.probe_step_ms(b, iters=iters)
                  for b in self.buckets}

  def step_ms(self, budget: int) -> float:
    scaled = budget / max(self.full_items, 1) * self.M
    nearest = min(self.buckets, key=lambda b: abs(b - scaled))
    return self.table[nearest]


def make_requests(arrivals_ms: Sequence[float], prompt_len: int,
                  max_new_tokens: int, vocab: int,
                  seed: int = 0) -> List[EngineRequest]:
  """Random-prompt requests at the given arrival offsets (ms)."""
  rng = np.random.default_rng(seed)
  return [EngineRequest(rid=i, arrival_ms=float(t),
                        prompt=rng.integers(0, vocab, prompt_len,
                                            dtype=np.int32),
                        max_new_tokens=max_new_tokens)
          for i, t in enumerate(arrivals_ms)]


def make_zipf_requests(arrivals_ms: Sequence[float], prompt_len: int,
                       max_new_tokens: int, vocab: int,
                       n_corpora: int = 8, alpha: float = 1.1,
                       seed: int = 0) -> List[EngineRequest]:
  """Zipf-repeated-corpora requests: each arrival draws its prompt from
  a fixed pool of ``n_corpora`` distinct corpora with Zipf(``alpha``)
  popularity — the shared-index / per-tenant-document workload shape the
  corpus cache exists for (DESIGN.md §12).  ``n_corpora=1`` is the
  100%-repeat arm (every admission after the first hits)."""
  rng = np.random.default_rng(seed)
  pool = [rng.integers(0, vocab, prompt_len, dtype=np.int32)
          for _ in range(n_corpora)]
  w = np.arange(1, n_corpora + 1, dtype=np.float64) ** -alpha
  picks = rng.choice(n_corpora, size=len(arrivals_ms), p=w / w.sum())
  return [EngineRequest(rid=i, arrival_ms=float(t),
                        prompt=pool[picks[i]],
                        max_new_tokens=max_new_tokens)
          for i, t in enumerate(arrivals_ms)]


def run_open_loop(engine: ServingEngine, rate_per_s: float,
                  duration_s: float, seed: int = 0,
                  slo_of=None, zipf_corpora: int = 0,
                  service_seed: Optional[int] = None) -> Dict[str, float]:
  """One measurement window of Poisson arrivals at ``rate_per_s`` — the
  engine-side mirror of ``ScatterGatherService.run_open_loop``.

  The window is draw-deterministic: the backend's interference/straggler
  RNG and injected fault plan (if any) are reseeded, so a re-run
  reproduces the same noise and fault sequence regardless of warmup or
  prior-window history (only the measured wall times themselves vary run
  to run).  ``slo_of(rid) -> str`` optionally assigns each request an
  SLO class (DESIGN.md §11).

  ``service_seed`` splits the two RNG roles ``seed`` used to play at
  once: arrivals and prompts ALWAYS derive from ``seed``, while the
  backend's service-side noise reseeds from ``service_seed`` when given
  (else ``seed``, the legacy coupling).  Sweep arms that must see the
  SAME arrival trace under independent service draws — the (contract,
  ε, rate) grids in benchmarks — pass a distinct ``service_seed`` per
  arm; sharing one seed across arms correlates the comparison's noise
  (the seed-reuse bug class; regression-tested in
  tests/test_estimator.py)."""
  engine.reset()
  if engine.backend is not None and hasattr(engine.backend, "reseed"):
    engine.backend.reseed(seed if service_seed is None else service_seed)
  arrivals = poisson_arrivals(rate_per_s, duration_s, seed=seed)
  if zipf_corpora > 0:
    reqs = make_zipf_requests(arrivals, engine.ecfg.prompt_len,
                              engine.ecfg.max_new_tokens, engine.cfg.vocab,
                              n_corpora=zipf_corpora, seed=seed)
  else:
    reqs = make_requests(arrivals, engine.ecfg.prompt_len,
                         engine.ecfg.max_new_tokens, engine.cfg.vocab,
                         seed=seed)
  if slo_of is not None:
    for r in reqs:
      r.slo = slo_of(r.rid)
  return engine.run(reqs)
