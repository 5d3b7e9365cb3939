"""Fleet tier: materialized-replica 2-D mesh serving (DESIGN.md §14).

The cluster tier (DESIGN.md §9/§10) replicates in the latency/accounting
plane only: a hedged gather is priced against a *modelled* replica while
the step program still reads the primary shard.  The fleet tier makes
replication real.  Components lay out on a ``("replica", "component")``
2-D mesh (`repro.dist.topology.plan_2d` / `make_fleet_mesh`): replica
row ``r`` holds, at mesh column ``j``, a **materialized** copy of shard
``shard_at(r, j) = (j - r) % N`` — row r is row 0 ring-rotated by r.

Materialization is free of any numerical caveat because the synopsis is
small (the paper's deployment premise) and the copy is pure data
movement: admission writes ONE arena (`kv_cache.ARENA_LEAVES`, shared
via the content-addressed corpus cache, DESIGN.md §12) and
`kv_cache.replicate_leaf` stacks R ring-rotated views of the scattered
shards — every replica copy is bit-identical to its primary, and each
mapping holds its own corpus-cache pin (`CorpusCache.acquire(n=R)`) so
retiring one replica's mapping can never free an arena another still
reads.

Per step the frontend runs *replica selection* (Tail-Tolerant
Distributed Search, arxiv 1707.07426; `topology.select_replica`): each
shard is served from whichever holder is predicted to finish first
under this step's interference/straggler draws, and the gather reads
the selected holder's **actual** shard — `make_fleet_attention` gathers
the selected (row, column) lane of every shard and folds the partials
in fixed shard order, so the result is bit-identical to the all-primary
gather whatever the selection (property-tested in tests/test_fleet.py).

Accounting prices shard c at the EARLIEST completion among its holders
(all R×N lanes execute in the CPU proxy, exactly as both sides of a
real hedge do): with R=2 and the same seed the fleet's per-shard time
equals the cluster tier's modelled-hedge min *identically*, which is
the deterministic CI gate — hedged-on-real-shard p99 can never exceed
modelled-hedge p99 at equal loss (benchmarks/fleet_bench.py).

The draw stream is unchanged from the cluster tier — exactly two noise
draws per step whatever R (rows r >= 1 share the reissue draw), so R=1,
cluster-R=2 and fleet-R=2 runs with the same seeds live in the same
noise world.

CPU-proxy caveat (EXPERIMENTS.md §Fleet): one host executes all R*N
lanes as one program; the measured wall is attributed per component by
corpus share + refined rows, and replica queueing is modelled by the
same draw discipline the cluster tier uses.  On a real fleet each mesh
row is a host group and the selection policy reads per-holder load.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.control import MODE_DROP, MODE_FULL, MODE_STAGE1
from repro.control.estimator import coverage_profile
from repro.dist import sharding as shd
from repro.dist.topology import make_fleet_mesh, plan_2d, select_replica
from repro.kernels import ops
from repro.serve import kv_cache as kvc
from repro.serve.cluster import (ClusterConfig, ClusterStepBackend, _StepPlan,
                                 _cluster_stacked, _extras_partial,
                                 _frontend_rank, _pick_mode, _select_local,
                                 allocate_budget, gain_rank)
from repro.serve.serve_step import make_serve_step

NEG_INF = ops.NEG_INF

__all__ = ["FleetConfig", "FleetStepBackend", "make_fleet_attention"]


@dataclasses.dataclass
class FleetConfig(ClusterConfig):
  """Fleet-tier knobs: a `ClusterConfig` whose ``replicas`` is a real
  mesh dimension (R >= 1 rows of materialized shards) instead of an
  accounting factor.  The resilience knobs must stay at their defaults
  — fault injection and the retry ladder ride the 1-D cluster tier;
  the fleet tier composes with them upstream (admission/shedding), not
  inside the gather."""
  replicas: int = 2


# ---------------------------------------------------------------------------
# The 2-D scatter-gather attention body.  Same math as the cluster tier:
# the ONLY new degree of freedom is WHICH materialized copy of each shard
# the gather reads (``fe_replica``), and every copy is bit-identical.
# ---------------------------------------------------------------------------

def _select_lanes(sel: jax.Array, N: int):
  """Mesh coordinates of each shard's selected holder: shard ``c`` served
  from replica row ``sel[c]`` lives at column ``(c + sel[c]) % N``."""
  cols = (jnp.arange(N, dtype=sel.dtype) + sel) % N
  return sel, cols


def make_fleet_attention(topo, alloc: str = "mass", mesh=None,
                         recirculate: bool = True, telemetry: bool = False):
  """Returns ``attention_fn(q, cache_sl, ...) -> (ctx, aux)`` over the
  replica-materialized cache layout (DESIGN.md §14):

    k/v          (B, Hkv, R, N, m_max*C, D)   ring-rotated shard copies
    k_syn/v_syn  (B, Hkv, R, N, m_max, D)
    counts       (B, R, N, m_max)
    fe_mode      (N,) int32                   per-shard gather mode
    fe_replica   (N,) int32                   per-shard selected holder

  Row 0 is exactly the cluster tier's 1-D layout; row r is row 0 rolled
  right by r along the component axis (`kv_cache.replicate_leaf`).

  Stacked execution gathers each shard's leaves from its selected
  (row, column) lane — pure indexing into bit-identical copies — and
  delegates to the cluster tier's `_cluster_stacked` fold.  Under a
  2-D mesh the shard_map body computes each lane's stage-1 + refinement
  locally, all-gathers scores and partials over both axes, and folds the
  selected lanes in fixed shard order — the same merge order as the
  stacked path, so both executions are bit-identical to the all-primary
  gather whatever ``fe_replica`` says."""

  def attention(q, csl, *, i_max, cluster_size, sm_scale, cap=None,
                self_kv=None, impl="xla"):
    if mesh is not None:
      return _fleet_sharded(
          q, csl, topo, alloc, mesh, i_max=i_max,
          cluster_size=cluster_size, sm_scale=sm_scale, cap=cap,
          self_kv=self_kv, impl=impl, recirculate=recirculate,
          telemetry=telemetry)
    return _fleet_stacked(
        q, csl, topo, alloc, i_max=i_max, cluster_size=cluster_size,
        sm_scale=sm_scale, cap=cap, self_kv=self_kv, impl=impl,
        recirculate=recirculate, telemetry=telemetry)

  return attention


def _fleet_stacked(q, csl, topo, alloc, *, i_max, cluster_size, sm_scale,
                   cap, self_kv, impl, recirculate=True, telemetry=False):
  """Single-device execution: gather every shard's leaves from its
  selected replica lane, then run the cluster tier's stacked body on the
  resulting 1-D component layout.  Selection is pure indexing into
  bit-identical copies, so the output cannot depend on it."""
  N = topo.n_components
  rows, cols = _select_lanes(csl["fe_replica"], N)
  flat = {kk: vv for kk, vv in csl.items() if kk != "fe_replica"}
  for name in ("k", "v", "k_syn", "v_syn", "k_syn_scale", "v_syn_scale",
               "k_scale", "v_scale"):
    if name not in csl:
      continue
    # Advanced indices at adjacent axes (replica, component) collapse to
    # one shard axis in shard order: entry c is shard c read from lane
    # (sel[c], (c + sel[c]) % N).
    flat[name] = csl[name][:, :, rows, cols]
  flat["counts"] = csl["counts"][:, rows, cols]
  return _cluster_stacked(
      q, flat, topo, alloc, i_max=i_max, cluster_size=cluster_size,
      sm_scale=sm_scale, cap=cap, self_kv=self_kv, impl=impl,
      recirculate=recirculate, mode_caps=False, telemetry=telemetry)


def _fleet_sharded(q, csl, topo, alloc, mesh, *, i_max, cluster_size,
                   sm_scale, cap, self_kv, impl, recirculate=True,
                   telemetry=False):
  """shard_map execution over the ``("replica", "component")`` mesh:
  device (r, j) holds shard ``(j - r) % N`` and runs its stage-1 +
  refinement locally; the frontend logic (rank, budgets, selection) runs
  replicated from the score all-gather, and the composer folds the
  SELECTED lane of every shard in fixed shard order — the same merge
  order as `_cluster_stacked`, hence bit-identical output."""
  from jax.sharding import PartitionSpec as P  # noqa: PLC0415
  N, Mp = topo.n_components, topo.m_max
  corpus = P(None, None, "replica", "component", None, None)
  specs = {"k": corpus, "v": corpus, "k_syn": corpus, "v_syn": corpus,
           "counts": P(None, "replica", "component", None),
           "fe_mode": P(), "fe_replica": P()}
  for name in ("k_syn_scale", "v_syn_scale", "k_scale", "v_scale"):
    if name in csl:
      specs[name] = P(None, None, "replica", "component", None)
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
    with shd.manual_axes({"replica", "component"}):
      rid = jax.lax.axis_index("replica")
      k_l, v_l = cache["k"][:, :, 0, 0], cache["v"][:, :, 0, 0]
      ks_l, vs_l = cache["k_syn"][:, :, 0, 0], cache["v_syn"][:, :, 0, 0]
      counts_l = cache["counts"][:, 0, 0]
      syn_scales = None if "k_syn_scale" not in cache else (
          cache["k_syn_scale"][:, :, 0, 0], cache["v_syn_scale"][:, :, 0, 0])
      kv_scales = None if "k_scale" not in cache else (
          cache["k_scale"][:, :, 0, 0], cache["v_scale"][:, :, 0, 0])
      mode = cache["fe_mode"]                       # (N,) replicated
      sel_arr = cache["fe_replica"]                 # (N,) replicated
      # The shard this lane holds: column j of row r is shard (j - r) % N.
      c_loc = (jax.lax.axis_index("component") - rid) % N

      sc_l, p_syn = ops.synopsis_stage1(
          q, ks_l, vs_l, counts_l, sm_scale=sm_scale, cap=cap, impl=impl,
          valid=counts_l > 0, syn_scales=syn_scales)
      # Scores within a row cover all N shards (a row is a rotation of
      # the full partition), in mesh-column order; rotate back to shard
      # order so every lane sees the same sc_all — copies are
      # bit-identical, so no cross-row gather is needed.
      sc = jax.lax.all_gather(sc_l, "component", axis=2, tiled=True)
      B, Hkv = sc.shape[:2]
      to_shard = (jnp.arange(N) + rid) % N
      sc_all = jnp.take(sc.reshape(B, Hkv, N, Mp), to_shard, axis=2)
      gsel, mass = _frontend_rank(sc_all, i_max)
      counts_g = None
      if alloc == "gain" or telemetry:
        cg = jax.lax.all_gather(cache["counts"][:, 0, 0], "component",
                                axis=1, tiled=True)
        counts_g = jnp.take(cg.reshape(B, N, Mp), to_shard, axis=1)
      if gsel is not None and alloc == "gain":
        gsel = gain_rank(sc_all, counts_g, i_max)

      if gsel is None:
        p_full = p_syn
        cover_l = jnp.zeros((1,), jnp.float32)
      else:
        budgets = None
        if alloc == "mass":
          caps = jnp.sum(sc_all > NEG_INF / 2, axis=-1)    # (B, Hkv, N)
          budgets = allocate_budget(mass, i_max, caps,
                                    recirculate=recirculate)
        sel = _select_local(c_loc, sc_l, gsel, budgets, alloc, i_max, Mp)
        p_ref = ops.refine_stage2(
            q, k_l, v_l, sel, ks_l, vs_l, counts_l,
            cluster_size=cluster_size, sm_scale=sm_scale, cap=cap,
            impl=impl, syn_scales=syn_scales, kv_scales=kv_scales)
        p_full = ops.merge_partials(p_syn, p_ref)
        cover_l = jnp.mean(
            jnp.sum((sel >= 0).astype(jnp.float32), -1))[None]
      contrib = _pick_mode(mode[c_loc], p_full, p_syn)

      def gather2(x):
        x = jax.lax.all_gather(x[None], "component", axis=0, tiled=True)
        return jax.lax.all_gather(x[None], "replica", axis=0, tiled=True)

      og, mg, lg = [gather2(x) for x in contrib]
      cols = (jnp.arange(N, dtype=sel_arr.dtype) + sel_arr) % N
      acc = None
      for c in range(N):
        # Fixed shard order c = 0..N-1 — the SAME merge order as the
        # stacked/cluster fold — reading shard c's selected lane.
        part = (og[sel_arr[c], cols[c]], mg[sel_arr[c], cols[c]],
                lg[sel_arr[c], cols[c]])
        acc = part if acc is None else ops.merge_partials(acc, part)
      p_ex = _extras_partial(q, cache, self_kv, sm_scale=sm_scale,
                             cap=cap, impl=impl)
      if p_ex is not None:
        acc = ops.merge_partials(acc, p_ex)
      cover2 = gather2(cover_l)[..., 0]              # (R, N) mesh coords
      cover = cover2[sel_arr, cols]                  # (N,) shard order
      mass_frac = mass / jnp.maximum(jnp.sum(mass, -1, keepdims=True),
                                     1e-30)
      outs = (acc[0], cover, jnp.mean(mass_frac, axis=(0, 1)))
      if telemetry:
        outs = outs + (coverage_profile(
            sc_all.reshape(B, Hkv, N * Mp), counts_g.reshape(B, N * Mp),
            rank="mass" if alloc == "gain" else "score"),)
      return outs

  n_out = 4 if telemetry else 3
  res = jax.shard_map(
      body, mesh=mesh, in_specs=(q_spec, specs, self_spec),
      out_specs=(P(),) * n_out,
      axis_names=frozenset({"replica", "component"}),
      check_vma=False)(q, csl, self_kv)
  aux = {"fe_cover": res[1], "fe_mass": res[2]}
  if telemetry:
    aux["est_profile"] = res[3]
  return res[0], aux


# ---------------------------------------------------------------------------
# ServingEngine step backend.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _FleetPlan(_StepPlan):
  """Cluster step plan + this step's per-shard replica selection."""
  sel: Optional[np.ndarray] = None       # (N,) int32 selected replica row


class FleetStepBackend(ClusterStepBackend):
  """Drop-in `ServingEngine` step backend running the fleet tier.

  Inherits the cluster tier's scatter/write/plan/account machinery and
  swaps in: a 2-D mesh (`make_fleet_mesh`), the replica-materializing
  slot write (`kv_cache.replicate_leaf` after scatter+route), the
  selection-aware attention body, and plan/account that price every
  shard at the earliest completion among its R materialized holders."""

  def bind(self, engine) -> None:
    super().bind(engine)
    cc = self.ccfg
    if self.resilient:
      raise ValueError(
          "fleet tier is non-resilient by construction (faults=None, "
          "retries=1, recovery=True): fault injection and the retry "
          "ladder ride the 1-D cluster tier")
    # Re-plan through the fleet entry point (validates R as a grid dim)
    # and upgrade the mesh to 2-D: R*N devices make replication real.
    self.topo = plan_2d(self.M, cc.n_components, cc.replicas, skew=cc.skew)
    self.mesh = make_fleet_mesh(cc.n_components, cc.replicas, cc.use_mesh)
    self.attention = make_fleet_attention(self.topo, alloc=cc.alloc,
                                          mesh=self.mesh,
                                          recirculate=cc.recirculate,
                                          telemetry=self.telemetry)
    self._write = self._make_write()

  @property
  def replica_mappings(self) -> int:
    """Pins per slot admission: each replica row maps the arena once
    (`ServingEngine` acquires/releases this many per slot)."""
    return self.topo.replicas

  # -- cache layout ----------------------------------------------------------
  def zeros_cache(self) -> Dict[str, jax.Array]:
    """Component layout with a replica axis: k-like leaves
    (nb, na, B, Hkv, R, N, ...) and counts (nb, na, B, R, N, Mp).  The
    batch/slot axis stays at 2, so the engine's admit/retire write path
    is untouched."""
    base = super().zeros_cache()
    R = self.topo.replicas
    for name in kvc.ARENA_LEAVES:
      if name not in base:
        continue
      x = base[name]
      ax = 3 if name == "counts" else 4
      base[name] = jnp.zeros(x.shape[:ax] + (R,) + x.shape[ax:], x.dtype)
    return base

  def _make_write(self):
    bx = kvc.slot_batch_axes(self.cfg, self.n_slots, self.prompt_len,
                             synopsis=True)
    rotate = self.ccfg.route == "rotate"
    R = self.ccfg.replicas

    def write(cache, syn, slot):
      # One arena write backs R replica mappings: scatter to components,
      # route (optional per-slot rotation), then stack the R ring-rotated
      # copies — pure data movement, bit-identical per copy.
      sub = self._scatter(syn)
      for name in kvc.ARENA_LEAVES:
        if name not in sub:
          continue
        ax = 3 if name == "counts" else 4
        if rotate:
          sub[name] = jnp.roll(sub[name], slot, axis=ax)
        sub[name] = kvc.replicate_leaf(sub[name], R, axis=ax)
      return kvc.write_slot(cache, sub, slot, bx)

    return jax.jit(write)

  # -- the compiled step -----------------------------------------------------
  def step_fn(self, budget: int):
    """The frontend vector is packed (2, N) int32 — row 0 the gather
    mode, row 1 the selected replica — so the engine's step dispatch
    signature is unchanged from the cluster tier."""
    step = make_serve_step(self.cfg, mode="synopsis", i_max=budget,
                           impl=self.impl, attention_fn=self.attention)

    @jax.jit
    def run(params, cache, tok, fe_mode):
      cache = dict(cache)
      cache["fe_mode"] = fe_mode[0]
      cache["fe_replica"] = fe_mode[1]
      return step(params, cache, tok)

    return run

  def full_mode(self) -> jax.Array:
    N = self.topo.n_components
    return jnp.stack([jnp.full((N,), MODE_FULL, jnp.int32),
                      jnp.zeros((N,), jnp.int32)])

  # -- frontend plan / account ----------------------------------------------
  def _replica_times(self, wall: float, u: np.ndarray, usum: float,
                     noise: np.ndarray, noise2: np.ndarray) -> np.ndarray:
    """(R, N) completion of shard c served from its r-th holder.  Row 0
    is the primary's own completion; row r >= 1 at holder j = (c+r)%N
    queues behind j's own shard (u[j] at noise[j] — the SAME draw that
    prices j's row-0 completion) then streams c's stage-1 + granted
    clusters (u[c]) under the reissue draw noise2[j].  Row 1 is exactly
    the cluster tier's `_hedge_time`, so fleet and cluster runs with the
    same seeds price the same world — rows share the two per-step draws
    whatever R."""
    N = self.topo.n_components
    c = np.arange(N)
    rows = [wall * (u / usum) * noise]
    for r in range(1, self.topo.replicas):
      j = (c + r) % N
      rows.append(wall * (u[j] * noise[j] + u * noise2[j]) / usum)
    return np.stack(rows)

  def plan_step(self, budget: int, step_deadline_ms: float) -> _FleetPlan:
    """Pre-dispatch decision: predict every (shard, holder) completion
    under this step's draws, select each shard's fastest holder
    (`select_replica` — ties to the primary), and let the policy mark
    shards whose BEST completion still misses the deadline STAGE1/DROP.
    The step program then reads the selected holders' actual shards."""
    massf = self.mass_ewma / max(self.mass_ewma.sum(), 1e-30)
    b_est = float(budget) * massf
    u = self._units(b_est)
    usum = max(u.sum(), 1e-30)
    noise, noise2 = self._draw_noise(), self._draw_noise()
    wall = self.predictor.predict(budget)
    t_rc = self._replica_times(wall, u, usum, noise, noise2)
    sel = select_replica(t_rc)
    t_best = t_rc.min(axis=0)
    mode, _ = self.engine.controller.gather_modes(t_best, step_deadline_ms)
    fe = jnp.asarray(np.stack([mode.astype(np.int32), sel]))
    return _FleetPlan(fe_mode=fe, mode=mode, noise=noise, noise2=noise2,
                      hedged=sel != 0, b_est=b_est,
                      deadline_ms=step_deadline_ms, sel=sel)

  def account(self, budget: int, wall_ms: float, plan: _FleetPlan, st,
              warming: bool = False) -> Dict[str, float]:
    """Post-step accounting: re-price the (R, N) completions with the
    measured wall and the actually-refined rows, and take each shard at
    its EARLIEST holder — every lane executes in the CPU proxy, exactly
    as both sides of a real hedge do, and the plan-time selection was
    argmin over the same expression, so the realized time can never be
    worse than the cluster tier's modelled hedge under the same draws
    (the deterministic gate in benchmarks/fleet_bench.py)."""
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
    u0 = self._units(np.zeros_like(cover))
    f0 = u0 / usum
    t_rc = self._replica_times(wall_ms, u, usum, plan.noise, plan.noise2)
    done_full = t_rc.min(axis=0)
    t_stage1 = wall_ms * f0 * plan.noise
    done = np.where(full, done_full,
                    np.where(plan.mode == MODE_STAGE1, t_stage1, 0.0))
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
    self.step_idx += 1
    off_primary = int((plan.sel != 0).sum()) if plan.sel is not None else 0
    return {"parallel_ms": parallel_ms, "step_acc": step_acc,
            "wall_ms": wall_ms, "gathered": int(full.sum()),
            "hedged": off_primary, "comp_ms": done,
            "drop_share": drop_share, "retried": 0,
            "off_primary": off_primary}
